"""Distributed Word2Vec — the dl4j-spark-nlp equivalent, TPU-native.

Parity surface: reference spark/dl4j-spark-nlp/.../embeddings/word2vec/
Word2Vec.java — Spark executors each train local embedding tables on their
RDD partition of sentences and the driver periodically combines them
(parameter-averaging semantics, same as ParameterAveragingTrainingMaster).

TPU design: ONE jitted shard_map program over the device mesh replaces the
whole executor/driver round trip. The shuffled (center, context) pair stream
is sharded over the 'data' axis; each device runs ``averaging_frequency``
skip-gram NEG batches on its own divergent copy of (syn0, syn1neg), then the
tables are pmean'd over ICI — the Spark combine step, but at microsecond
cost and inside the compiled epoch (no host round trips at all). With one
device the pmean is the identity and this degenerates to the single-chip
epoch scan.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map

from deeplearning4j_tpu.nlp.word2vec import Word2Vec, _sg_neg_batch


def _build_epoch(mesh: Mesh, negative: int):
    """(C, K, nB) batches → trained (syn0, syn1neg); C outer chunks of K
    local steps (K implicit in the batch shapes), table pmean per chunk."""

    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(), P(), P(None, None, "data"),
                       P(None, None, "data"), P(None, None, "data"),
                       P(), P()),
             out_specs=(P(), P()),
             check_vma=False)
    def epoch(syn0, syn1, table, centers, contexts, weights, lrs, key):
        # per-device negative-sampling stream
        key = jax.random.fold_in(key, lax.axis_index("data"))

        def chunk(carry, inp):
            syn0, syn1, key = carry
            cs, ts, ws, lr_row = inp          # (K, local_B) / (K,)

            def local_step(c2, inp2):
                syn0, syn1, key = c2
                c, t, w, lr = inp2
                key, sub = jax.random.split(key)
                syn0, syn1 = _sg_neg_batch(syn0, syn1, table, c, t, lr, sub,
                                           negative, weights=w)
                return (syn0, syn1, key), jnp.float32(0)

            (syn0, syn1, key), _ = lax.scan(local_step, (syn0, syn1, key),
                                            (cs, ts, ws, lr_row))
            # the Spark combine step: average divergent replica tables
            syn0 = lax.pmean(syn0, "data")
            syn1 = lax.pmean(syn1, "data")
            return (syn0, syn1, key), jnp.float32(0)

        (syn0, syn1, _), _ = lax.scan(chunk, (syn0, syn1, key),
                                      (centers, contexts, weights, lrs))
        return syn0, syn1

    return jax.jit(epoch, donate_argnums=(0, 1))


class DistributedWord2Vec(Word2Vec):
    """Word2Vec trained data-parallel over a device mesh (parity: the Spark
    Word2Vec; SURVEY.md §2 #24). Only skip-gram + negative sampling — the
    configuration the reference's Spark implementation optimizes for."""

    def __init__(self, *args, mesh: Optional[Mesh] = None,
                 averaging_frequency: int = 8, scale_lr: bool = True,
                 **kwargs):
        kwargs.setdefault("elements_learning_algorithm", "skipgram")
        super().__init__(*args, **kwargs)
        if self.use_hs or self.algorithm != "skipgram":
            raise NotImplementedError(
                "DistributedWord2Vec supports skip-gram with negative "
                "sampling only (the configuration the reference's Spark "
                "implementation optimizes for)")
        if mesh is None:
            from deeplearning4j_tpu.parallel.wrapper import default_mesh
            mesh = default_mesh()
        self.mesh = mesh
        self.averaging_frequency = max(1, averaging_frequency)
        # averaging n divergent replicas applies each local update at 1/n
        # weight; linear LR scaling restores the effective step size (the
        # classic data-parallel LR rule — disable with scale_lr=False)
        self.scale_lr = scale_lr
        self._epoch_fn = None

    def fit(self):
        if self.vocab is None:
            self.build_vocab()
        if self.syn0 is None:
            self._init_tables()
        seqs = self._encode_corpus()
        rng = np.random.RandomState(self.seed + 31)
        key = jax.random.PRNGKey(self.seed)

        centers_all, contexts_all = self._make_pairs(seqs, rng)
        if len(centers_all) == 0:          # nothing to train on (all
            self._norm_cache = None        # sequences < 2 tokens)
            return self
        n_dev = self.mesh.devices.size
        k = self.averaging_frequency
        bs = max(n_dev, self._effective_batch() // n_dev * n_dev)
        n_pairs = len(centers_all)
        steps_per_epoch = max(1, (n_pairs + bs - 1) // bs)
        # pad each epoch to C chunks of K batches of bs pairs (pad weight 0);
        # the LR schedule must count the k-rounded S steps or later epochs
        # start past total_steps and clamp to min_learning_rate
        C = (steps_per_epoch + k - 1) // k
        S = C * k
        total_steps = self.epochs * S
        if self._epoch_fn is None:
            self._epoch_fn = _build_epoch(self.mesh, self.negative)

        step_i = 0
        for ep in range(self.epochs):
            order = rng.permutation(n_pairs)
            pad = S * bs - n_pairs
            sel = np.concatenate([order, np.zeros(pad, order.dtype)])
            w = np.concatenate([np.ones(n_pairs, np.float32),
                                np.zeros(pad, np.float32)])
            lr0 = self.learning_rate * (n_dev if self.scale_lr else 1)
            lrs = np.maximum(
                self.min_learning_rate,
                lr0 * (1.0 - (step_i + np.arange(S)) / total_steps)
            ).astype(np.float32)
            key, sub = jax.random.split(key)
            self.syn0, self.syn1 = self._epoch_fn(
                self.syn0, self.syn1, self._table,
                jnp.asarray(centers_all[sel].reshape(C, k, bs)),
                jnp.asarray(contexts_all[sel].reshape(C, k, bs)),
                jnp.asarray(w.reshape(C, k, bs)),
                jnp.asarray(lrs.reshape(C, k)), sub)
            step_i += S
        self._norm_cache = None
        return self
