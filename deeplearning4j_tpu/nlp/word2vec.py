"""Word2Vec — skip-gram / CBOW with negative sampling or hierarchical softmax.

Parity surface: reference models/word2vec/Word2Vec.java (builder),
models/embeddings/learning/impl/elements/SkipGram.java (287 LoC) + CBOW.java,
InMemoryLookupTable (syn0/syn1/syn1neg/expTable), subsampling + lr decay
(SequenceVectors.fit :192).

TPU design: the reference's VectorCalculationsThreads do lock-free scalar
updates through the native AggregateSkipGram op. Here the corpus is converted
into (center, context) index batches on host; ONE jit'd step per batch does
gather → dot → sigmoid → scatter-add on device arrays. Negative samples are
drawn on device from the unigram table. This turns a memory-latency-bound
scalar workload into batched vector ops — the TPU-idiomatic formulation.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nlp.vocab import (
    VocabCache, VocabConstructor, build_huffman, unigram_table,
)
from deeplearning4j_tpu.nlp.tokenization import (
    DefaultTokenizerFactory, CommonPreprocessor,
)


def _lr_schedule(xp, lr0, lr_min, step0, S, total):
    """Linear LR decay clamped at ``lr_min`` — THE schedule formula for
    every NEG path. Host planning (``_epoch_plan``) calls it with numpy,
    the fused device fit (``_sg_neg_fit``) with jax.numpy; one formula, two
    array modules, no copies to diverge."""
    return xp.maximum(
        lr_min,
        lr0 * (1.0 - (step0 + xp.arange(S, dtype=xp.float32)) / total))


def _sg_neg_batch_shared(syn0, syn1neg, table, centers, contexts, lr, key,
                         negative, weights=None):
    """Skip-gram NEG batch with BATCH-SHARED negative samples: one draw of
    ``negative`` indices serves every pair in the batch (candidate sharing,
    the standard trick of sampled-softmax / large-batch word2vec GPU
    implementations). The unigram sampling distribution is unchanged in
    expectation; what changes is that a batch's pairs see the same
    candidates — over thousands of steps the variance washes out (the
    embedding-quality tests train through this path).

    Why: per-pair negatives cost B*K gathered + scattered table rows per
    batch — the row-rate of TPU gather/scatter was the measured word2vec
    ceiling. Shared negatives turn all negative traffic into three small
    MATMULs (scores (B,D)@(D,K), input grads (B,K)@(K,D), table grads
    (K,B)@(B,D)) and a K-row update — MXU work instead of scatter."""
    v = syn0[centers]                      # (B, D)
    u_pos = syn1neg[contexts]              # (B, D)
    s_pos = jax.nn.sigmoid((v * u_pos).sum(-1))
    g_pos = (1.0 - s_pos) * lr
    if weights is not None:
        g_pos = g_pos * weights
    dv = g_pos[:, None] * u_pos
    du_pos = g_pos[:, None] * v
    negs = table[jax.random.randint(key, (negative,), 0, table.shape[0])]
    u_neg = syn1neg[negs]                  # (K, D)
    s_neg = jax.nn.sigmoid(v @ u_neg.T)    # (B, K)
    g_neg = -s_neg * lr
    if weights is not None:
        g_neg = g_neg * weights[:, None]
    dv = dv + g_neg @ u_neg                # (B, D)
    du_neg = g_neg.T @ v                   # (K, D)
    syn0 = syn0.at[centers].add(dv)
    syn1neg = syn1neg.at[contexts].add(du_pos)
    syn1neg = syn1neg.at[negs].add(du_neg)
    return syn0, syn1neg


def _sg_neg_batch(syn0, syn1neg, table, centers, contexts, lr, key, negative,
                  weights=None):
    """One skip-gram negative-sampling batch (traceable core).
    centers/contexts: (B,) int32; weights: optional (B,) 0/1 pair weights
    (0 = padding pair contributing nothing). Returns (syn0, syn1neg)."""
    B = centers.shape[0]
    v = syn0[centers]                      # (B, D)
    # positive pair
    u_pos = syn1neg[contexts]              # (B, D)
    s_pos = jax.nn.sigmoid((v * u_pos).sum(-1))
    g_pos = (1.0 - s_pos) * lr             # (B,)
    if weights is not None:
        g_pos = g_pos * weights
    dv = g_pos[:, None] * u_pos
    du_pos = g_pos[:, None] * v
    # negatives: (B, K) draws from the unigram table
    idx = jax.random.randint(key, (B, negative), 0, table.shape[0])
    negs = table[idx]                      # (B, K)
    u_neg = syn1neg[negs]                  # (B, K, D)
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", v, u_neg))
    g_neg = -s_neg * lr                    # (B, K)
    if weights is not None:
        g_neg = g_neg * weights[:, None]
    dv = dv + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    du_neg = g_neg[..., None] * v[:, None, :]
    # scatter updates (duplicate indices accumulate); positive-context and
    # negative-sample rows go through ONE fused scatter on syn1neg
    syn0 = syn0.at[centers].add(dv)
    all_idx = jnp.concatenate([contexts, negs.reshape(-1)])
    all_du = jnp.concatenate([du_pos, du_neg.reshape(B * negative, -1)])
    syn1neg = syn1neg.at[all_idx].add(all_du)
    return syn0, syn1neg


@partial(jax.jit,
         static_argnames=("negative", "bs", "shared", "packed", "epochs"),
         donate_argnums=(0, 1))
def _sg_neg_fit(syn0, syn1neg, table, pairs, lr0, lr_min, key, negative, bs,
                shared=True, packed=False, epochs=1):
    """ALL epochs of NEG skip-gram in one dispatch: outer scan over epochs
    (fresh device-side shuffle each), inner scan over batches. One pair
    transfer + one dispatch per fit() — every host->device scalar or array
    costs a dispatch the tiny per-batch update cannot hide, so the entire
    training loop lives on device."""
    if packed:
        centers = (pairs & 0xFFFF).astype(jnp.int32)
        contexts = (pairs >> 16).astype(jnp.int32)
    else:
        centers, contexts = pairs[0], pairs[1]
    n = centers.shape[0]
    S = -(-n // bs)
    pad = S * bs - n
    total = jnp.float32(max(1, epochs * S))
    step_fn = _sg_neg_batch_shared if shared else _sg_neg_batch

    def epoch_body(carry, ep):
        syn0, syn1neg, key = carry
        key, kperm = jax.random.split(key)
        idx = jax.random.permutation(kperm, n)
        sel = jnp.concatenate([idx, jnp.zeros(pad, idx.dtype)])
        w = jnp.concatenate([jnp.ones(n, jnp.float32),
                             jnp.zeros(pad, jnp.float32)]).reshape(S, bs)
        c = centers[sel].reshape(S, bs)
        t = contexts[sel].reshape(S, bs)
        lrs = _lr_schedule(jnp, lr0, lr_min, ep * S, S, total)

        def body(carry2, inp):
            syn0, syn1neg, key = carry2
            cc, tt, ww, lr = inp
            key, sub = jax.random.split(key)
            syn0, syn1neg = step_fn(syn0, syn1neg, table, cc, tt, lr, sub,
                                    negative, weights=ww)
            return (syn0, syn1neg, key), jnp.float32(0)

        (syn0, syn1neg, key), _ = jax.lax.scan(
            body, (syn0, syn1neg, key), (c, t, w, lrs))
        return (syn0, syn1neg, key), jnp.float32(0)

    (syn0, syn1neg, _), _ = jax.lax.scan(
        epoch_body, (syn0, syn1neg, key),
        jnp.arange(epochs, dtype=jnp.float32))
    return syn0, syn1neg


@partial(jax.jit, static_argnames=("negative",), donate_argnums=(0, 1))
def _sg_neg_epoch(syn0, syn1neg, table, centers_b, contexts_b, weights_b,
                  lrs, key, negative):
    """A whole epoch of skip-gram NEG batches in ONE compiled lax.scan —
    one dispatch instead of one per batch, which matters enormously on
    high-latency device attachments (~100ms RPC per transfer here).
    centers_b/contexts_b/weights_b: (S, B); lrs: (S,) per-batch LR."""
    def body(carry, inp):
        syn0, syn1neg, key = carry
        c, t, w, lr = inp
        key, sub = jax.random.split(key)
        syn0, syn1neg = _sg_neg_batch(syn0, syn1neg, table, c, t, lr, sub,
                                      negative, weights=w)
        return (syn0, syn1neg, key), jnp.float32(0)

    (syn0, syn1neg, _), _ = jax.lax.scan(
        body, (syn0, syn1neg, key), (centers_b, contexts_b, weights_b, lrs))
    return syn0, syn1neg


@partial(jax.jit, static_argnames=("negative",), donate_argnums=(0,))
def _sg_infer_step(dv, syn1neg, table, docs, words, lr, key, negative):
    """Skip-gram step that updates ONLY the doc/center table (syn1neg is
    frozen and NOT donated) — used by ParagraphVectors.infer_vector."""
    v = dv[docs]
    u_pos = syn1neg[words]
    s_pos = jax.nn.sigmoid((v * u_pos).sum(-1))
    g_pos = (1.0 - s_pos) * lr
    delta = g_pos[:, None] * u_pos
    idx = jax.random.randint(key, (docs.shape[0], negative), 0, table.shape[0])
    negs = table[idx]
    u_neg = syn1neg[negs]
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", v, u_neg))
    delta = delta + jnp.einsum("bk,bkd->bd", -s_neg * lr, u_neg)
    return dv.at[docs].add(delta)


@partial(jax.jit, static_argnames=("normalize",), donate_argnums=(0, 1))
def _sg_hs_step(syn0, syn1, centers, points, codes, code_mask, lr, *,
                normalize=False):
    """Skip-gram hierarchical-softmax batch.
    points/codes/code_mask: (B, L) padded Huffman paths of the CONTEXT word;
    centers: (B,) input word indices.

    ``normalize=True`` divides each scatter-add by the index's occurrence
    count in the batch. The reference applies pairs sequentially, so a
    vertex/word hit many times self-limits through the updated sigmoid;
    a batched scatter-add SUMS co-located gradients instead — on dense
    small graphs (DeepWalk's regime) the Huffman root collects thousands of
    summed updates and the tables diverge without this."""
    v = syn0[centers]                      # (B, D)
    u = syn1[points]                       # (B, L, D)
    s = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", v, u))
    # grad of -log p: (1 - code - sigmoid)
    g = (1.0 - codes - s) * lr * code_mask
    dv = jnp.einsum("bl,bld->bd", g, u)
    du = g[..., None] * v[:, None, :]
    B, L = points.shape
    flat_p = points.reshape(-1)
    du = du.reshape(B * L, -1)
    if normalize:
        cnt_c = jnp.zeros((syn0.shape[0],), jnp.float32).at[centers].add(1.0)
        dv = dv / cnt_c[centers][:, None]
        cnt_p = jnp.zeros((syn1.shape[0],), jnp.float32).at[flat_p].add(
            code_mask.reshape(-1))
        du = du / jnp.maximum(cnt_p[flat_p], 1.0)[:, None]
    syn0 = syn0.at[centers].add(dv)
    syn1 = syn1.at[flat_p].add(du)
    return syn0, syn1


def _cbow_neg_batch(syn0, syn1neg, table, context_mat, context_mask, targets,
                    lr, key, negative, weights=None):
    """CBOW traceable core: mean of context vectors predicts the target.
    context_mat: (B, W) int32 padded window indices; context_mask: (B, W);
    weights: optional (B,) 0/1 row weights (0 = padding row)."""
    B, W = context_mat.shape
    ctx = syn0[context_mat]                      # (B, W, D)
    denom = jnp.maximum(context_mask.sum(-1, keepdims=True), 1.0)
    h = (ctx * context_mask[..., None]).sum(1) / denom   # (B, D)
    u_pos = syn1neg[targets]
    s_pos = jax.nn.sigmoid((h * u_pos).sum(-1))
    g_pos = (1.0 - s_pos) * lr
    if weights is not None:
        g_pos = g_pos * weights
    dh = g_pos[:, None] * u_pos
    du_pos = g_pos[:, None] * h
    idx = jax.random.randint(key, (B, negative), 0, table.shape[0])
    negs = table[idx]
    u_neg = syn1neg[negs]
    s_neg = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, u_neg))
    g_neg = -s_neg * lr
    if weights is not None:
        g_neg = g_neg * weights[:, None]
    dh = dh + jnp.einsum("bk,bkd->bd", g_neg, u_neg)
    du_neg = g_neg[..., None] * h[:, None, :]
    # distribute dh back to context words (divided by window count)
    dctx = (dh / denom)[:, None, :] * context_mask[..., None]
    syn0 = syn0.at[context_mat.reshape(-1)].add(dctx.reshape(B * W, -1))
    syn1neg = syn1neg.at[targets].add(du_pos)
    syn1neg = syn1neg.at[negs.reshape(-1)].add(du_neg.reshape(B * negative, -1))
    return syn0, syn1neg


def _cbow_hs_batch(syn0, syn1, context_mat, context_mask, points, codes,
                   code_mask, lr, weights=None):
    """CBOW + hierarchical softmax batch (parity: reference
    nlp/.../embeddings/learning/impl/elements/CBOW.java:138 — the
    codes/points branch of iterateSample, on the mean context vector).
    Reuses the SG-HS math (_sg_hs_step) with the input side swapped from a
    single center vector to the masked context mean, and the Huffman path
    taken from the TARGET word: points/codes/code_mask: (B, L)."""
    B, W = context_mat.shape
    ctx = syn0[context_mat]                      # (B, W, D)
    denom = jnp.maximum(context_mask.sum(-1, keepdims=True), 1.0)
    h = (ctx * context_mask[..., None]).sum(1) / denom   # (B, D)
    u = syn1[points]                             # (B, L, D)
    s = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, u))
    g = (1.0 - codes - s) * lr * code_mask       # grad of -log p
    if weights is not None:
        g = g * weights[:, None]
    dh = jnp.einsum("bl,bld->bd", g, u)
    du = g[..., None] * h[:, None, :]
    dctx = (dh / denom)[:, None, :] * context_mask[..., None]
    syn0 = syn0.at[context_mat.reshape(-1)].add(dctx.reshape(B * W, -1))
    syn1 = syn1.at[points.reshape(-1)].add(du.reshape(-1, du.shape[-1]))
    return syn0, syn1


@partial(jax.jit, donate_argnums=(0, 1))
def _cbow_hs_epoch(syn0, syn1, ctxs_b, masks_b, pts_b, cds_b, cmsk_b,
                   weights_b, lrs):
    """A whole epoch of CBOW-HS batches in ONE compiled lax.scan.
    ctxs_b/masks_b: (S, B, W); pts_b/cds_b/cmsk_b: (S, B, L);
    weights_b: (S, B); lrs: (S,)."""
    def body(carry, inp):
        syn0, syn1 = carry
        c, m, p, cd, cm, w, lr = inp
        syn0, syn1 = _cbow_hs_batch(syn0, syn1, c, m, p, cd, cm, lr,
                                    weights=w)
        return (syn0, syn1), jnp.float32(0)

    (syn0, syn1), _ = jax.lax.scan(
        body, (syn0, syn1), (ctxs_b, masks_b, pts_b, cds_b, cmsk_b,
                             weights_b, lrs))
    return syn0, syn1


@partial(jax.jit, static_argnames=("negative",), donate_argnums=(0, 1))
def _cbow_neg_epoch(syn0, syn1neg, table, ctxs_b, masks_b, targets_b,
                    weights_b, lrs, key, negative):
    """A whole epoch of CBOW batches in ONE compiled lax.scan (see
    _sg_neg_epoch). ctxs_b/masks_b: (S, B, W); targets_b/weights_b: (S, B);
    lrs: (S,)."""
    def body(carry, inp):
        syn0, syn1neg, key = carry
        c, m, t, w, lr = inp
        key, sub = jax.random.split(key)
        syn0, syn1neg = _cbow_neg_batch(syn0, syn1neg, table, c, m, t, lr,
                                        sub, negative, weights=w)
        return (syn0, syn1neg, key), jnp.float32(0)

    (syn0, syn1neg, _), _ = jax.lax.scan(
        body, (syn0, syn1neg, key), (ctxs_b, masks_b, targets_b, weights_b,
                                     lrs))
    return syn0, syn1neg


class Word2Vec:
    """Builder-style Word2Vec (parity: Word2Vec.Builder)."""

    def __init__(self, min_word_frequency=5, layer_size=100, window_size=5,
                 learning_rate=0.025, min_learning_rate=1e-4, negative=5,
                 use_hierarchic_softmax=False, epochs=1, batch_size=4096,
                 subsampling=1e-3, seed=123, elements_learning_algorithm="skipgram",
                 iterate=None, tokenizer_factory=None, sentences=None,
                 negative_sharing=True):
        """``negative_sharing=True`` (default) draws each batch's negative
        samples once for the whole batch (candidate sharing) — same unigram
        distribution in expectation, ~3x throughput on TPU because negative
        gathers/scatters become matmuls. This is a documented SEMANTIC
        divergence from the reference, not just a speedup: batch-shared
        negatives correlate the negative term across the batch's pairs,
        which raises gradient variance per step (embedding quality on the
        test corpora is indistinguishable). Set False for the reference's
        strict per-pair sampling (SkipGram.java draws per pair) — e.g. for
        parity audits or very small batches, where the correlation is
        proportionally larger."""
        self.min_word_frequency = min_word_frequency
        self.layer_size = layer_size
        self.window_size = window_size
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        self.epochs = epochs
        self.batch_size = batch_size
        self.subsampling = subsampling
        self.seed = seed
        self.algorithm = elements_learning_algorithm.lower()
        self.iterate = iterate
        self.sentences = sentences
        self.negative_sharing = negative_sharing
        self.tokenizer_factory = tokenizer_factory or \
            DefaultTokenizerFactory().set_token_pre_processor(CommonPreprocessor())
        self.vocab: Optional[VocabCache] = None
        self.syn0 = None
        self.syn1 = None
        self._norm_cache = None

    # ----------------------------------------------------------- vocab + data
    def _sequences(self):
        if self.sentences is not None:
            src = self.sentences
        elif self.iterate is not None:
            src = self.iterate
        else:
            raise ValueError("No corpus: provide sentences=[...] or iterate=")
        for s in src:
            toks = self.tokenizer_factory.create(s).get_tokens()
            if toks:
                yield toks

    def build_vocab(self):
        self.vocab = VocabConstructor(self.min_word_frequency).build_vocab(
            self._sequences())
        if self.use_hs:
            build_huffman(self.vocab)
        return self

    def _init_tables(self):
        rng = np.random.RandomState(self.seed)
        V, D = self.vocab.num_words(), self.layer_size
        self.syn0 = jnp.asarray(
            (rng.rand(V, D).astype(np.float32) - 0.5) / D)
        self.syn1 = jnp.zeros((V, D), jnp.float32)
        self._table = jnp.asarray(unigram_table(self.vocab), jnp.int32)

    def _keep_probs(self) -> np.ndarray:
        """Per-vocab-index subsampling keep probability (Mikolov formula,
        parity: the reference's per-word ``ran`` threshold)."""
        vocab = self.vocab
        total = max(vocab.total_word_count, 1)
        counts = np.array([vocab._by_index[i].count
                           for i in range(vocab.num_words())], np.float64)
        if not self.subsampling or self.subsampling <= 0:
            return np.ones(len(counts))
        f = counts / total
        with np.errstate(divide="ignore", invalid="ignore"):
            p = (np.sqrt(f / self.subsampling) + 1) * self.subsampling / f
        return np.minimum(np.nan_to_num(p, nan=1.0, posinf=1.0), 1.0)

    def _encode_tokens(self):
        """Tokenize + vocab-index the whole corpus ONCE, cached across
        ``fit()`` calls for the same corpus object + vocab. The reference
        re-streams its SentenceIterator every epoch because its JVM worker
        threads consume text lazily; with an in-memory corpus the token →
        index resolution is deterministic, so re-tokenizing each fit/epoch
        is pure waste (it dominated wall time before this cache). Returns
        (flat int32 indices incl. -1 for OOV, per-sentence lengths)."""
        src = self.sentences if self.sentences is not None else self.iterate
        if isinstance(src, (list, tuple)):
            # content fingerprint: CPython caches each str's hash, so this
            # is one dict-speed pass — catches in-place corpus mutation
            # (same list object, new sentences) that an id()-only key would
            # silently miss
            # tokenizer/preprocessor identity is part of the signature:
            # swapping the factory between fits must invalidate the cache
            sig = (id(self.vocab), id(self.tokenizer_factory),
                   id(getattr(self.tokenizer_factory, "preprocessor", None)),
                   len(src), hash(tuple(map(hash, src))))
        else:
            # non-indexable corpora (SentenceIterator-style) are streamed
            # fresh every fit — no safe identity to cache on
            sig = None
        if sig is not None and getattr(self, "_tok_cache", None) is not None \
                and self._tok_sig == sig:
            return self._tok_cache
        index_of = self.vocab.index_of
        memo = {}
        arrs = []
        for toks in self._sequences():
            a = np.empty(len(toks), np.int32)
            for k, t in enumerate(toks):
                i = memo.get(t)
                if i is None:
                    i = index_of(t)
                    memo[t] = i
                a[k] = i
            arrs.append(a)
        flat = np.concatenate(arrs) if arrs else np.zeros(0, np.int32)
        lens = np.array([len(a) for a in arrs], np.int64)
        self._tok_cache = (flat, lens)
        self._tok_sig = sig
        return self._tok_cache

    def _encode_flat(self):
        """(kept tokens, sentence ids) after per-fit subsampling — the flat
        corpus view every pair/window generator consumes, produced without
        per-sentence numpy-call overhead (one vectorized bernoulli + masks
        over the cached token stream)."""
        flat, lens = self._encode_tokens()
        if flat.size == 0:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32))
        rng = np.random.RandomState(self.seed + 17)
        p_keep = self._keep_probs()
        keep = (flat >= 0) & (rng.rand(flat.size)
                              < p_keep[np.maximum(flat, 0)])
        sids = np.repeat(np.arange(len(lens), dtype=np.int32), lens)
        return flat[keep], sids[keep]

    def _encode_corpus(self):
        """Corpus → list of index arrays with per-fit subsampling (kept for
        the HS / CBOW / GloVe / ParagraphVectors consumers; the NEG
        skip-gram hot path uses ``_encode_flat`` directly)."""
        flat_k, sids_k = self._encode_flat()
        if flat_k.size == 0:
            return []
        # re-split at sentence-id boundaries
        bounds = np.nonzero(np.diff(sids_k))[0] + 1
        return [s for s in np.split(flat_k, bounds) if s.size > 1]

    @staticmethod
    def _flatten(seqs):
        """List of index arrays → (flat tokens, sentence ids)."""
        flat = np.concatenate(seqs) if seqs else np.zeros(0, np.int32)
        sids = np.repeat(np.arange(len(seqs), dtype=np.int32),
                         [len(s) for s in seqs]) if seqs else \
            np.zeros(0, np.int32)
        return flat, sids

    def _make_pairs(self, seqs, rng):
        flat, sids = self._flatten(seqs)
        return self._make_pairs_flat(flat, sids, rng)

    def _make_pairs_flat(self, flat, sids, rng):
        """(center, context) pairs with the reference's randomized effective
        window (b = random in [1, window] per CENTER), vectorized: one numpy
        pass per window offset over the flattened corpus instead of a Python
        loop per token (the reference parallelizes the same loop across
        VectorCalculationsThreads; here the loop disappears entirely)."""
        n = len(flat)
        if n == 0:
            return (np.zeros(0, np.int32), np.zeros(0, np.int32))
        wins = rng.randint(1, self.window_size + 1, size=n)
        cs, ts = [], []
        for d in range(1, self.window_size + 1):
            if d >= n:
                break
            same = sids[:-d] == sids[d:]
            # center i, context i+d (right neighbor within i's window)
            i = np.nonzero(same & (wins[:-d] >= d))[0]
            cs.append(flat[i])
            ts.append(flat[i + d])
            # center i+d, context i (left neighbor within (i+d)'s window)
            j = np.nonzero(same & (wins[d:] >= d))[0] + d
            cs.append(flat[j])
            ts.append(flat[j - d])
        if not cs:        # corpus reduced to a single token: no pairs
            return (np.zeros(0, np.int32), np.zeros(0, np.int32))
        return (np.concatenate(cs).astype(np.int32),
                np.concatenate(ts).astype(np.int32))

    def _effective_batch(self):
        """Batched scatter-adds accumulate duplicate-pair updates linearly,
        where sequential SGD would damp them as sigmoid saturates; with a
        small vocab this overshoots and collapses the embedding. Cap the
        batch at 8x vocab so duplicates per batch stay few; large real
        vocabularies keep the full batch."""
        return max(64, min(self.batch_size, 8 * self.vocab.num_words()))

    # ------------------------------------------------------------------- fit
    def _huffman_tables(self):
        """Padded (V, L) Huffman path tables (points, codes, mask) for the
        HS paths — one row per vocab word."""
        L = max((len(w.codes) for w in self.vocab.vocab_words()), default=1)
        V = self.vocab.num_words()
        pts = np.zeros((V, L), np.int32)
        cds = np.zeros((V, L), np.float32)
        msk = np.zeros((V, L), np.float32)
        for w in self.vocab.vocab_words():
            l = len(w.codes)
            # points are inner-node ids; clip negatives (root offset) to 0..V-1
            pts[w.index, :l] = np.clip(w.points, 0, V - 1)
            cds[w.index, :l] = w.codes
            msk[w.index, :l] = 1.0
        return jnp.asarray(pts), jnp.asarray(cds), jnp.asarray(msk)

    def fit(self):
        if self.vocab is None:
            self.build_vocab()
        if self.syn0 is None:
            self._init_tables()
        rng = np.random.RandomState(self.seed + 31)
        key = jax.random.PRNGKey(self.seed)

        if not self.use_hs and self.algorithm != "cbow":
            # NEG skip-gram hot path: flat corpus view straight into the
            # device-shuffled epoch scan (no per-sentence lists, no host
            # permutation/padding/selection)
            flat_k, sids_k = self._encode_flat()
            centers_all, contexts_all = self._make_pairs_flat(flat_k, sids_k,
                                                              rng)
            n_pairs = len(centers_all)
            if n_pairs == 0:
                self._norm_cache = None
                return self
            bs = self._effective_batch()
            packed = self.vocab.num_words() < 2 ** 15
            if packed:
                pj = jnp.asarray(centers_all.astype(np.int32)
                                 | (contexts_all.astype(np.int32) << 16))
            else:
                pj = jnp.asarray(
                    np.stack([centers_all, contexts_all]).astype(np.int32))
            key, sub = jax.random.split(key)
            self.syn0, self.syn1 = _sg_neg_fit(
                self.syn0, self.syn1, self._table, pj,
                jnp.float32(self.learning_rate),
                jnp.float32(self.min_learning_rate), sub,
                self.negative, bs, self.negative_sharing, packed,
                self.epochs)
            self._norm_cache = None
            return self

        seqs = self._encode_corpus()

        if self.algorithm == "cbow":
            # CBOW trains on (window, target) batches only — running the
            # skip-gram pair loop as well would double-train syn0
            # (_fit_cbow handles both NEG and HS objectives)
            self._fit_cbow(seqs, rng, key)
            self._norm_cache = None
            return self

        pts_j, cds_j, msk_j = self._huffman_tables()

        centers_all, contexts_all = self._make_pairs(seqs, rng)
        bs = self._effective_batch()
        n_pairs = len(centers_all)
        total_steps = max(1, self.epochs * ((n_pairs + bs - 1) // bs))
        step_i = 0
        for ep in range(self.epochs):
            order = rng.permutation(n_pairs)
            for s in range(0, n_pairs, bs):
                sel = order[s:s + bs]
                lr = max(self.min_learning_rate,
                         self.learning_rate * (1.0 - step_i / total_steps))
                c = jnp.asarray(centers_all[sel])
                t = jnp.asarray(contexts_all[sel])
                key, sub = jax.random.split(key)
                self.syn0, self.syn1 = _sg_hs_step(
                    self.syn0, self.syn1, c, pts_j[t], cds_j[t], msk_j[t],
                    jnp.float32(lr))
                step_i += 1

        self._norm_cache = None
        return self

    def _make_cbow_windows(self, seqs, rng, with_sids=False):
        """Vectorized (contexts, mask, targets[, sequence ids]) window
        matrices: one numpy pass per offset, mirroring _make_pairs.
        ``with_sids`` also returns each kept row's sequence index
        (ParagraphVectors uses it as the document id)."""
        W = self.window_size
        flat, sids = self._flatten(seqs)
        n = len(flat)
        ctxs = np.zeros((n, 2 * W), np.int32)
        masks = np.zeros((n, 2 * W), np.float32)
        if n:
            wins = rng.randint(1, W + 1, size=n)
            for d in range(1, W + 1):
                if d >= n:
                    break
                same = sids[:-d] == sids[d:]
                # left neighbor i-d of center i → column d-1
                li = np.nonzero(same & (wins[d:] >= d))[0] + d
                ctxs[li, d - 1] = flat[li - d]
                masks[li, d - 1] = 1.0
                # right neighbor i+d of center i → column W+d-1
                ri = np.nonzero(same & (wins[:-d] >= d))[0]
                ctxs[ri, W + d - 1] = flat[ri + d]
                masks[ri, W + d - 1] = 1.0
        keep = masks.sum(axis=1) > 0
        out = (ctxs[keep], masks[keep], flat[keep].astype(np.int32))
        if with_sids:
            out = out + (sids[keep].astype(np.int32),)
        return out

    def _epoch_plan(self, n, bs, order, step_i, total_steps):
        """One epoch's HOST-side scan inputs, or None when the corpus
        yields nothing to train on (n == 0): (S, (S,bs) padded selection,
        (S,bs) 0/1 pad weights, (S,) LR schedule). Used by the CBOW /
        ParagraphVectors / distributed paths; the NEG skip-gram hot path
        builds the same plan ON DEVICE in ``_sg_neg_fit`` — both draw the
        decay from ``_lr_schedule`` so the formula cannot fork."""
        if n == 0:
            return None
        S = (n + bs - 1) // bs
        pad = S * bs - n
        sel = np.concatenate([order, np.zeros(pad, order.dtype)])
        w = np.concatenate([np.ones(n, np.float32), np.zeros(pad, np.float32)])
        lrs = _lr_schedule(np, self.learning_rate, self.min_learning_rate,
                           step_i, S, max(total_steps, 1)).astype(np.float32)
        return S, sel.reshape(S, bs), w.reshape(S, bs), lrs

    def _fit_cbow(self, seqs, rng, key):
        """CBOW pass: each epoch's (window, target) batches run in one
        compiled scan (same dispatch-amortization as the skip-gram path).
        use_hierarchic_softmax selects the Huffman-path objective
        (CBOW.java:138 codes/points branch) instead of negative sampling."""
        ctxs, masks, targets = self._make_cbow_windows(seqs, rng)
        n = len(targets)
        bs = self._effective_batch()
        total = self.epochs * max(1, (n + bs - 1) // bs)
        step_i = 0
        if self.use_hs:
            pts_j, cds_j, msk_j = self._huffman_tables()
        for ep in range(self.epochs):
            order = np.random.RandomState(self.seed + ep).permutation(n)
            plan = self._epoch_plan(n, bs, order, step_i, total)
            if plan is None:
                return
            S, sel, w, lrs = plan
            key, sub = jax.random.split(key)
            if self.use_hs:
                t = jnp.asarray(targets[sel])
                self.syn0, self.syn1 = _cbow_hs_epoch(
                    self.syn0, self.syn1, jnp.asarray(ctxs[sel]),
                    jnp.asarray(masks[sel]), pts_j[t], cds_j[t], msk_j[t],
                    jnp.asarray(w), jnp.asarray(lrs))
            else:
                self.syn0, self.syn1 = _cbow_neg_epoch(
                    self.syn0, self.syn1, self._table, jnp.asarray(ctxs[sel]),
                    jnp.asarray(masks[sel]), jnp.asarray(targets[sel]),
                    jnp.asarray(w), jnp.asarray(lrs), sub, self.negative)
            step_i += S

    # ------------------------------------------------------------ query API
    def word_vector(self, word) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def get_word_vector_matrix(self) -> np.ndarray:
        return np.asarray(self.syn0)

    def has_word(self, word) -> bool:
        return self.vocab is not None and self.vocab.contains_word(word)

    def _normed(self):
        if self._norm_cache is None:
            m = np.asarray(self.syn0)
            self._norm_cache = m / np.maximum(
                np.linalg.norm(m, axis=1, keepdims=True), 1e-9)
        return self._norm_cache

    def similarity(self, w1, w2) -> float:
        i, j = self.vocab.index_of(w1), self.vocab.index_of(w2)
        if i < 0 or j < 0:
            return float("nan")
        n = self._normed()
        return float(n[i] @ n[j])

    def words_nearest(self, word, n=10) -> List[str]:
        if isinstance(word, str):
            i = self.vocab.index_of(word)
            if i < 0:
                return []
            q = self._normed()[i]
            exclude = {i}
        else:
            q = np.asarray(word, np.float64)
            q = q / max(np.linalg.norm(q), 1e-9)
            exclude = set()
        sims = self._normed() @ q
        order = np.argsort(-sims)
        out = []
        for idx in order:
            if idx in exclude:
                continue
            out.append(self.vocab.word_at_index(int(idx)))
            if len(out) >= n:
                break
        return out
