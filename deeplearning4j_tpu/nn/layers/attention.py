"""Attention layers.

The reference (DL4J 0.9.2) has NO attention layer — long sequences are
handled only by truncated BPTT (SURVEY.md §5 'long-context'). This module is
the TPU-first extension the build plan calls for: scaled-dot-product
multi-head attention that slots into the Layer protocol, with a
sequence-parallel ring-attention path (parallel/sequence_parallel.py) for
contexts longer than one chip's HBM.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import Layer, register_layer, require_dims
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.nn.conf.inputs import InputType


def scaled_dot_product_attention(q, k, v, *, causal=False, mask=None,
                                 q_offset=0, k_offset=0, train=False):
    """q/k/v: (B, T, H, Dh). mask: (B, Tk) key padding mask. Offsets give
    global positions for causal masking of sequence blocks. ``train``
    feeds the route decision: the flash kernel is a custom-vjp pair, so a
    training call commits BOTH its forward and backward — routing asks
    for both phases (exec/routing.py flash_attn_route)."""
    from deeplearning4j_tpu import ops
    if (mask is None and q_offset == 0 and k_offset == 0
            and q.shape == k.shape and v.shape == q.shape
            and ops.helpers_enabled()):
        from deeplearning4j_tpu.ops.flash_attention import (
            supported, MIN_SEQ_FOR_AUTO_ROUTE)
        from deeplearning4j_tpu.exec.routing import flash_attn_route
        B, T, H, Dh = q.shape
        # interpreter mode (CPU tests) exercises the kernel at any length;
        # compiled mode routes per (shape, backend) measurement with the
        # long-sequence crossover as the no-data fallback — the SAME
        # decision for the training and inference forward
        interp = ops.interpret_mode()
        min_t = 0 if interp else MIN_SEQ_FOR_AUTO_ROUTE
        backend = None if interp else jax.default_backend()
        if (supported(T, Dh, min_t=0)
                and flash_attn_route(B * H, T, Dh, causal, train=train,
                                     backend=backend,
                                     min_t=min_t) == "pallas"):
            dt = q.dtype
            fold = lambda a: (a.transpose(0, 2, 1, 3)
                              .reshape(B * H, T, Dh).astype(jnp.float32))
            o = ops.flash_attention(fold(q), fold(k), fold(v), causal,
                                    interp)
            return (o.reshape(B, H, T, Dh).transpose(0, 2, 1, 3).astype(dt))
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        qpos = jnp.arange(q.shape[1]) + q_offset
        kpos = jnp.arange(k.shape[1]) + k_offset
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
    if mask is not None:
        s = jnp.where(mask[:, None, None, :] > 0, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@register_layer
@dataclass
class MultiHeadAttention(Layer):
    """Self-attention over (B, T, C) with n_heads heads. Param keys:
    Wq/Wk/Wv/Wo (+ biases). Projections are single fused GEMMs on the MXU."""
    n_in: int = 0
    n_out: int = 0          # model dim (defaults to n_in)
    n_heads: int = 4
    causal: bool = False
    has_bias: bool = True

    # KV caches are POSITIONAL decode state: rows are indexed by token
    # position and guarded by the causal mask, so speculative rewind
    # (serving/spec/) never snapshots them — rejected positions are
    # simply overwritten before any read can reach them.
    positional_state_keys = ("k", "v", "pk", "pv")

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.size or input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in

    def output_type(self, input_type):
        return InputType.recurrent(self.n_out or self.n_in,
                                   input_type.timeseries_length)

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in, n_out=self.n_out or self.n_in)
        if self.n_out == 0:
            self.n_out = self.n_in
        if self.n_out % self.n_heads != 0:
            raise ValueError(f"n_out={self.n_out} not divisible by "
                             f"n_heads={self.n_heads}")
        keys = jax.random.split(rng, 4)
        wi = self.weight_init or "xavier"
        p = {
            "Wq": init_weights(keys[0], (self.n_in, self.n_out), wi, self.dist, dtype),
            "Wk": init_weights(keys[1], (self.n_in, self.n_out), wi, self.dist, dtype),
            "Wv": init_weights(keys[2], (self.n_in, self.n_out), wi, self.dist, dtype),
            "Wo": init_weights(keys[3], (self.n_out, self.n_out), wi, self.dist, dtype),
        }
        if self.has_bias:
            p["bq"] = jnp.zeros((self.n_out,), dtype)
            p["bk"] = jnp.zeros((self.n_out,), dtype)
            p["bv"] = jnp.zeros((self.n_out,), dtype)
            p["bo"] = jnp.zeros((self.n_out,), dtype)
        return p

    def _project(self, params, x):
        B, T, _ = x.shape
        H = self.n_heads
        Dh = self.n_out // H
        q = x @ params["Wq"]
        k = x @ params["Wk"]
        v = x @ params["Wv"]
        if self.has_bias:
            q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
        return (q.reshape(B, T, H, Dh), k.reshape(B, T, H, Dh),
                v.reshape(B, T, H, Dh))

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        B, T, _ = x.shape
        q, k, v = self._project(params, x)
        o = scaled_dot_product_attention(q, k, v, causal=self.causal,
                                         mask=mask, train=train)
        o = o.reshape(B, T, self.n_out) @ params["Wo"]
        if self.has_bias:
            o = o + params["bo"]
        return o, state

    # ---- incremental decode ----------------------------------------------
    def init_decode_state(self, params, batch, max_len, dtype=jnp.float32):
        """Fixed-capacity KV cache: (B, max_len, H, Dh) per tensor. Capacity
        equals the full-forward sequence length, so the decode softmax runs
        over the same-length axis as teacher forcing (masked positions are
        -inf → exp 0) and stays bitwise-equal to it."""
        H = self.n_heads
        Dh = (self.n_out or self.n_in) // H
        # two distinct buffers — sharing one array would make the engine's
        # donated step donate the same buffer twice
        return {"k": jnp.zeros((batch, max_len, H, Dh), dtype),
                "v": jnp.zeros((batch, max_len, H, Dh), dtype)}

    def _finish_step(self, params, q, kc, vc, pos):
        """Shared decode-step attention math over a gathered/dense cache
        ``kc``/``vc`` (B, C, H, Dh) — the ONE copy of the parity-oracle
        path, so the paged gather stays byte-identical to the dense slot
        step by construction."""
        B = q.shape[0]
        C = kc.shape[1]
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc) * scale     # (B, H, 1, C)
        valid = jnp.arange(C)[None, :] <= pos[:, None]       # (B, C)
        s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        # Bitwise parity trick: XLA:CPU lowers the q-length-1 contraction as
        # a gemv whose accumulation order differs from the full forward's
        # gemm rows in the last ulp. Broadcasting the single query row to 2
        # rows forces the gemm path (rows are independent, so row 0 equals
        # the teacher-forced row exactly); the duplicate row is one extra
        # (C, Dh) dot per head — noise next to the step's dispatch cost.
        p2 = jnp.broadcast_to(p, (B, p.shape[1], 2, C))
        o = jnp.einsum("bhqk,bkhd->bqhd", p2, vc)[:, :1]
        o = o.reshape(B, 1, self.n_out) @ params["Wo"]
        if self.has_bias:
            o = o + params["bo"]
        return o

    def _project_out(self, params, o, B, T, dt):
        o = o.reshape(B, T, self.n_out).astype(dt) @ params["Wo"]
        if self.has_bias:
            o = o + params["bo"]
        return o

    def decode_step(self, params, dstate, x, pos, state=None):
        if not self.causal:
            raise ValueError(
                "only causal attention can decode incrementally (non-causal "
                "heads attend to future tokens)")
        B = x.shape[0]
        q, k, v = self._project(params, x)              # (B, 1, H, Dh)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        rows = jnp.arange(B)
        kc = dstate["k"].at[rows, pos].set(k[:, 0])
        vc = dstate["v"].at[rows, pos].set(v[:, 0])
        C = kc.shape[1]
        from deeplearning4j_tpu import ops
        if ops.helpers_enabled():
            from deeplearning4j_tpu.exec import decode_attn_route
            from deeplearning4j_tpu.ops import flash_decode
            Dh = q.shape[-1]
            # interpret mode exercises the kernel on any backend (tests);
            # compiled mode asks routing with the real platform
            backend = None if ops.interpret_mode() else jax.default_backend()
            if (flash_decode.supported(C, Dh)
                    and decode_attn_route(C, Dh, backend=backend)
                    == "pallas"):
                # flash decode-step: reads only pos+1 of the C cached rows
                o = ops.flash_decode_step(q[:, 0], kc, vc, pos,
                                          interpret=ops.interpret_mode())
                return (self._project_out(params, o, B, 1, q.dtype),
                        {"k": kc, "v": vc})
        return self._finish_step(params, q, kc, vc, pos), {"k": kc, "v": vc}

    # ---- paged decode (serving/kv/) --------------------------------------
    def init_paged_decode_state(self, params, batch, max_len, num_blocks,
                                block_size, dtype=jnp.float32):
        """KV block pool (kv/pool.py layout): (num_blocks, block_size, H,
        Dh) per tensor, shared by every slot and addressed through the
        engine's page tables. Keys 'pk'/'pv' (kv.POOL_KEYS) mark the
        leaves the engine's per-slot wipe/freeze masks must skip."""
        H = self.n_heads
        Dh = (self.n_out or self.n_in) // H
        return {"pk": jnp.zeros((num_blocks, block_size, H, Dh), dtype),
                "pv": jnp.zeros((num_blocks, block_size, H, Dh), dtype)}

    def decode_step_paged(self, params, dstate, x, pos, block_tables,
                          state=None):
        """Decode step against the block pool: scatter this position's KV
        into its ``pos → (block, offset)`` pool row, then either run the
        paged flash kernel (table-indexed DMA inside the kernel loop) or
        gather the logical cache and run the byte-identical dense math —
        the parity oracle the bitwise tests pin. Inactive slots carry
        all-zero tables, so their writes land in the reserved scratch
        block; the softmax position mask keeps scratch rows out of every
        real slot's attention."""
        if not self.causal:
            raise ValueError(
                "only causal attention can decode incrementally (non-causal "
                "heads attend to future tokens)")
        B = x.shape[0]
        q, k, v = self._project(params, x)              # (B, 1, H, Dh)
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        bs = dstate["pk"].shape[1]
        MB = block_tables.shape[1]
        rows = jnp.arange(B)
        phys = block_tables[rows, pos // bs]            # (B,) pool block
        off = pos % bs
        pk = dstate["pk"].at[phys, off].set(k[:, 0])
        pv = dstate["pv"].at[phys, off].set(v[:, 0])
        C = MB * bs
        from deeplearning4j_tpu import ops
        if ops.helpers_enabled():
            from deeplearning4j_tpu.exec import decode_attn_route
            from deeplearning4j_tpu.ops import flash_decode
            Dh = q.shape[-1]
            interp = ops.interpret_mode()
            backend = None if interp else jax.default_backend()
            if (flash_decode.supported_paged(bs, Dh, self.n_heads,
                                             interpret=interp)
                    and decode_attn_route(C, Dh, backend=backend,
                                          paged=True) == "pallas"):
                o = ops.flash_decode_step_paged(
                    q[:, 0], pk, pv, pos, block_tables, interpret=interp)
                return (self._project_out(params, o, B, 1, q.dtype),
                        {"pk": pk, "pv": pv})
        kc = pk[block_tables].reshape(B, C, *pk.shape[2:])
        vc = pv[block_tables].reshape(B, C, *pv.shape[2:])
        return (self._finish_step(params, q, kc, vc, pos),
                {"pk": pk, "pv": pv})

    def prefill_chunk(self, params, dstate, x, start, n, state=None,
                      block_tables=None, carry_stack=False):
        """Chunked prefill: scatter the chunk's K rows of KV into their
        cache positions, gather the logical cache, and run the same
        causal-masked softmax/gemm the full forward runs — bitwise-equal
        to teacher forcing row-for-row (the (K, C) gemm's rows are
        independent, like the decode trick's 2-row gemm).

        Paged (``"pk"`` in dstate): rows past a slot's ``n`` scatter into
        the scratch block and produce garbage activations the engine
        discards. Dense: the cache is updated with a position-aligned
        gather+where instead of a scatter, so padding rows (whose clipped
        positions could collide with real writes) are masked out
        deterministically. ``carry_stack`` always returns a None stack —
        KV state is positional, never snapshotted (see Layer)."""
        if dstate is None:
            return super().prefill_chunk(params, dstate, x, start, n,
                                         state=state,
                                         block_tables=block_tables,
                                         carry_stack=carry_stack)
        B, K, _ = x.shape
        q, k, v = self._project(params, x)              # (B, K, H, Dh)
        poss = start[:, None] + jnp.arange(K)[None, :]  # (B, K) positions
        valid = jnp.arange(K)[None, :] < n[:, None]
        rows = jnp.arange(B)
        if "pk" in dstate:
            bs = dstate["pk"].shape[1]
            MB = block_tables.shape[1]
            C = MB * bs
            bidx = jnp.clip(poss // bs, 0, MB - 1)
            phys = jnp.where(valid, block_tables[rows[:, None], bidx], 0)
            off = poss % bs
            pk = dstate["pk"].at[phys, off].set(k)
            pv = dstate["pv"].at[phys, off].set(v)
            # gather AFTER the scatter: chunk rows attend causally to rows
            # written in this same chunk, exactly like teacher forcing
            kc = pk[block_tables].reshape(B, C, *pk.shape[2:])
            vc = pv[block_tables].reshape(B, C, *pv.shape[2:])
            nd = {"pk": pk, "pv": pv}
        else:
            C = dstate["k"].shape[1]
            # position-aligned update: cache position c takes chunk row
            # c - start when that row is valid, else keeps its old value
            coff = jnp.arange(C)[None, :] - start[:, None]       # (B, C)
            wr = (coff >= 0) & (coff < jnp.minimum(n, K)[:, None])
            tidx = jnp.broadcast_to(
                jnp.clip(coff, 0, K - 1)[:, :, None, None],
                (B, C) + k.shape[2:])

            def upd(cache, new):
                g = jnp.take_along_axis(new, tidx, axis=1)
                return jnp.where(wr[:, :, None, None], g, cache)

            kc = upd(dstate["k"], k)
            vc = upd(dstate["v"], v)
            nd = {"k": kc, "v": vc}
        scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(q.dtype)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kc) * scale   # (B, H, K, C)
        causal = jnp.arange(C)[None, None, :] <= poss[:, :, None]
        s = jnp.where(causal[:, None, :, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if K == 1:   # single-row chunk: same gemv hazard as the decode step
            p = jnp.broadcast_to(p, (B, p.shape[1], 2, C))
            o = jnp.einsum("bhqk,bkhd->bqhd", p, vc)[:, :1]
        else:
            o = jnp.einsum("bhqk,bkhd->bqhd", p, vc)
        o = o.reshape(B, K, self.n_out) @ params["Wo"]
        if self.has_bias:
            o = o + params["bo"]
        return (o, nd, None) if carry_stack else (o, nd)

    # ---- tree speculation (serving/spec/tree.py) -------------------------
    def tree_chunk(self, params, dstate, x, pos0, tree, n, state=None,
                   block_tables=None):
        """Ancestry-masked attention over N tree nodes WITHOUT touching
        the cache. Sibling nodes share stream positions, so scattering
        the window's KV before acceptance (what ``prefill_chunk`` does
        for a linear window) would collide; instead each node attends to
        its EFFECTIVE cache — the real cache with positions
        ``pos0 .. pos0+depth(n)`` replaced by the node's own root-path
        K/V (``tree.anc_at_depth`` row n). That cache is element-for-
        element the cache the plain engine would hold after feeding that
        path, and the math is ``_finish_step`` itself over B*N rows, so
        every node's output is bitwise the non-speculative step's output
        for its prefix — the lossless-acceptance bar. The winning path's
        rows commit in ``tree_commit``; rejected nodes never existed as
        far as the cache is concerned."""
        if dstate is None:
            return super().tree_chunk(params, dstate, x, pos0, tree, n,
                                      state=state,
                                      block_tables=block_tables)
        B, N, _ = x.shape
        q, k, v = self._project(params, x)              # (B, N, H, Dh)
        H, Dh = k.shape[2], k.shape[3]
        if "pk" in dstate:
            bs = dstate["pk"].shape[1]
            C = block_tables.shape[1] * bs
            kc = dstate["pk"][block_tables].reshape(B, C, H, Dh)
            vc = dstate["pv"][block_tables].reshape(B, C, H, Dh)
        else:
            kc, vc = dstate["k"], dstate["v"]
            C = kc.shape[1]
        depth = jnp.asarray(tree.depth, jnp.int32)       # (N,)
        aad = jnp.asarray(tree.anc_at_depth, jnp.int32)  # (N, D+1)
        Dp1 = aad.shape[1]
        coff = jnp.arange(C)[None, :] - pos0[:, None]    # (B, C)
        # cache position pos0+dd holds the node's depth-dd ancestor
        on_path = ((coff[:, None, :] >= 0)
                   & (coff[:, None, :] <= depth[None, :, None]))  # (B,N,C)
        didx = jnp.broadcast_to(
            jnp.clip(coff, 0, Dp1 - 1)[:, None, :, None, None],
            (B, N, C, H, Dh))

        def effective(cache, win):
            path = win[:, aad]                           # (B, N, D+1, H, Dh)
            g = jnp.take_along_axis(path, didx, axis=2)  # (B, N, C, H, Dh)
            return jnp.where(on_path[..., None, None], g,
                             cache[:, None])

        effk = effective(kc, k)
        effv = effective(vc, v)
        posn = pos0[:, None] + depth[None, :]            # (B, N)
        o = self._finish_step(params,
                              q.reshape(B * N, 1, H, Dh),
                              effk.reshape(B * N, C, H, Dh),
                              effv.reshape(B * N, C, H, Dh),
                              posn.reshape(B * N))
        return (o.reshape(B, N, self.n_out), dstate, None,
                {"k": k, "v": v})

    def tree_commit(self, params, dstate, kv_window, path, pos0, commit_n,
                    block_tables=None):
        """Scatter the accepted root-path's K/V into the cache at
        positions ``pos0 + d`` for ``d < commit_n`` — the only tree
        writes that ever reach the cache. Paged rows outside the commit
        mask land in the scratch block (the inert-row discipline of
        ``prefill_chunk``); dense rows use a gather-old/where update so
        masked depths rewrite their current value bit-for-bit."""
        B, Dp1 = path.shape
        rows = jnp.arange(B)
        poss = pos0[:, None] + jnp.arange(Dp1)[None, :]  # (B, D+1)
        valid = jnp.arange(Dp1)[None, :] < commit_n[:, None]
        nidx = jnp.broadcast_to(path[:, :, None, None],
                                (B, Dp1) + kv_window["k"].shape[2:])
        kg = jnp.take_along_axis(kv_window["k"], nidx, axis=1)
        vg = jnp.take_along_axis(kv_window["v"], nidx, axis=1)
        if "pk" in dstate:
            bs = dstate["pk"].shape[1]
            MB = block_tables.shape[1]
            bidx = jnp.clip(poss // bs, 0, MB - 1)
            phys = jnp.where(valid, block_tables[rows[:, None], bidx], 0)
            off = poss % bs
            return {"pk": dstate["pk"].at[phys, off].set(kg),
                    "pv": dstate["pv"].at[phys, off].set(vg)}
        C = dstate["k"].shape[1]
        cpos = jnp.clip(poss, 0, C - 1)
        gidx = jnp.broadcast_to(cpos[:, :, None, None],
                                (B, Dp1) + kg.shape[2:])

        def upd(cache, new):
            old = jnp.take_along_axis(cache, gidx, axis=1)
            val = jnp.where(valid[:, :, None, None], new, old)
            return cache.at[rows[:, None], cpos].set(val)

        return {"k": upd(dstate["k"], kg), "v": upd(dstate["v"], vg)}


@register_layer
@dataclass
class LayerNormalization(Layer):
    """Layer norm over the feature axis (companion to attention stacks)."""
    n_in: int = 0
    eps: float = 1e-5

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.size or input_type.flat_size()

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in)
        return {"gamma": jnp.ones((self.n_in,), dtype),
                "beta": jnp.zeros((self.n_in,), dtype)}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        xn = (x - mean) * jax.lax.rsqrt(var + self.eps)
        return xn * params["gamma"] + params["beta"], state


@register_layer
@dataclass
class PositionalEmbedding(Layer):
    """Learned absolute positional embedding added to (B, T, C) inputs —
    attention is permutation-invariant over a position's prefix, so a
    transformer stack needs this (or rotary) to see token order. Companion
    to MultiHeadAttention; no reference equivalent (the reference has no
    attention at all)."""
    n_in: int = 0
    max_len: int = 512

    def set_n_in(self, input_type):
        if self.n_in == 0:
            self.n_in = input_type.size or input_type.flat_size()

    def init(self, rng, dtype=jnp.float32):
        require_dims(self, n_in=self.n_in)
        return {"P": jax.random.normal(rng, (self.max_len, self.n_in),
                                       dtype) * 0.02}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        T = x.shape[1]
        if T > self.max_len:
            raise ValueError(f"sequence length {T} exceeds "
                             f"max_len={self.max_len}")
        return x + params["P"][:T], state

    def decode_step(self, params, dstate, x, pos, state=None):
        B = x.shape[0]
        pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
        return x + params["P"][pos][:, None, :], dstate

    def prefill_chunk(self, params, dstate, x, start, n, state=None,
                      block_tables=None, carry_stack=False):
        """Chunk rows sit at global positions ``start + t``, not ``t`` —
        the stateless default's ``apply`` would add P[0:K]."""
        K = x.shape[1]
        poss = start[:, None] + jnp.arange(K)[None, :]   # (B, K)
        poss = jnp.clip(poss, 0, self.max_len - 1)
        y = x + params["P"][poss]
        return (y, dstate, None) if carry_stack else (y, dstate)

    def tree_chunk(self, params, dstate, x, pos0, tree, n, state=None,
                   block_tables=None):
        """Tree node n sits at stream position ``pos0 + depth(n)`` — the
        stateless default's ``apply`` would add P[0:N] by node index."""
        poss = pos0[:, None] + jnp.asarray(tree.depth, jnp.int32)[None, :]
        poss = jnp.clip(poss, 0, self.max_len - 1)
        return x + params["P"][poss], dstate, None, None
