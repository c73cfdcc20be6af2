"""Layer base protocol + registry + JSON serde.

Replaces the reference's two-sided design (declarative nn/conf/layers/*.java
config POJOs + imperative nn/layers/** Layer impls with hand-written
``backpropGradient``, nn/api/Layer.java:38): here a layer is ONE dataclass
whose ``apply`` is a pure traced function; autodiff provides the backward.

Protocol:
- ``set_n_in(input_type)``  — infer input width (parity:
  MultiLayerConfiguration.setInputType nIn inference).
- ``output_type(input_type)`` — shape inference.
- ``init(rng, dtype)`` — params pytree ({} if parameterless).
- ``init_state()`` — non-trainable state pytree ({} if stateless; batchnorm
  running stats live here, carried functionally through the train step).
- ``apply(params, x, state=…, train=…, rng=…, mask=…)`` →
  ``(y, new_state)``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional, Any, Dict

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.updaters import Updater
from deeplearning4j_tpu.nn.conf.inputs import InputType

LAYER_REGISTRY: Dict[str, type] = {}


def register_layer(cls):
    LAYER_REGISTRY[cls.__name__] = cls
    return cls


# fields every layer may inherit from the global NeuralNetConfiguration
INHERITABLE = ("activation", "weight_init", "updater", "l1", "l2", "dropout",
               "bias_init", "dist", "weight_noise")


@dataclass
class Layer:
    """Base layer config. ``None`` hyperparameters inherit the network-level
    defaults at build time (parity: NeuralNetConfiguration.Builder global
    defaults, NeuralNetConfiguration.java:570)."""
    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    dist: Optional[tuple] = None            # for weight_init='distribution'
    bias_init: Optional[float] = None
    updater: Optional[Updater] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    dropout: Optional[float] = None          # drop probability (NOT dl4j retain-prob)
    weight_noise: Optional[object] = None    # IWeightNoise (DropConnect/...)
    constraints: Optional[tuple] = None      # e.g. ('maxnorm', 2.0)

    # ---- config protocol -------------------------------------------------
    def apply_defaults(self, defaults: Dict[str, Any]):
        for f in INHERITABLE:
            if hasattr(self, f) and getattr(self, f) is None and f in defaults:
                setattr(self, f, defaults[f])

    def validate(self) -> None:
        """Fail fast on unknown activation/loss names at config-build time
        (parity: the reference's enums make these unrepresentable)."""
        from deeplearning4j_tpu.nn.activations import get_activation
        if getattr(self, "activation", None) is not None:
            get_activation(self.activation)
        if getattr(self, "loss", None) is not None:
            from deeplearning4j_tpu.nn.losses import get_loss
            get_loss(self.loss)

    def set_n_in(self, input_type: InputType) -> None:
        pass

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    # ---- runtime protocol ------------------------------------------------
    def init(self, rng, dtype=jnp.float32) -> Dict[str, Any]:
        return {}

    def init_state(self, dtype=jnp.float32) -> Dict[str, Any]:
        return {}

    def apply(self, params, x, state=None, *, train=False, rng=None, mask=None):
        raise NotImplementedError

    # ---- incremental decode protocol (serving/decode.py) -----------------
    # Autoregressive serving feeds ONE token per call; layers that carry
    # sequence context expose it as explicit decode state so the whole
    # stack becomes a fixed-shape (B, 1, F) → (B, 1, F) step the containers
    # can jit exactly once. Stateless layers (dense, norm, activations)
    # inherit these defaults: no state, apply() on the length-1 slice.

    # Decode-state dict keys that are POSITIONAL: written at an explicit
    # position index (attention KV caches), so speculative rewind
    # (serving/spec/) can leave over-written positions in place and rely
    # on the causal position mask — only NON-positional leaves (recurrent
    # carries) need snapshot/rollback. Plain class attribute, not a
    # dataclass field.
    positional_state_keys = ()

    # A layer's own term of the step's loss: the key of the scalar in the
    # state its training-mode ``apply`` returns, which both containers'
    # ``_loss`` add to the output layers' scores (an attention layer's
    # indexer loss; an expert layer's balancing loss would come the same
    # way). None: no term. Plain class attribute or property, not a
    # dataclass field.
    loss_state = None

    def init_decode_state(self, params, batch: int, max_len: int,
                          dtype=jnp.float32):
        """Per-slot decode state for a batch of ``batch`` concurrent
        streams (None = stateless). RNNs return the (h, c) carry; attention
        returns a fixed-capacity KV cache of ``max_len`` positions."""
        return None

    def decode_step(self, params, dstate, x, pos, state=None):
        """One incremental token step. ``x``: (B, 1, F) activations for the
        current position; ``pos``: (B,) int32 global position of that token
        per stream. Returns ``(y, new_dstate)`` with y (B, 1, F_out).
        Must be bitwise-equal to the same position of a full-sequence
        ``apply`` (decode correctness bar — see docs/DECODING.md)."""
        y, _ = self.apply(params, x, state, train=False, rng=None)
        return y, dstate

    # ---- paged decode protocol (serving/kv/) -----------------------------
    # The paged engine stores attention KV in a shared block pool indexed
    # by per-slot page tables instead of per-slot dense strips. Layers
    # WITHOUT a KV cache keep per-slot state exactly as in the dense
    # protocol, so the defaults delegate; MultiHeadAttention overrides all
    # three (pool-shaped state, table-gather step, chunk prefill).
    def init_paged_decode_state(self, params, batch: int, max_len: int,
                                num_blocks: int, block_size: int,
                                dtype=jnp.float32):
        """Decode state under paged KV: attention returns pool arrays
        ((num_blocks, block_size, H, Dh) — keys in kv.POOL_KEYS); every
        other layer returns its dense per-slot state unchanged."""
        return self.init_decode_state(params, batch, max_len, dtype)

    def decode_step_paged(self, params, dstate, x, pos, block_tables,
                          state=None):
        """``decode_step`` with a (B, max_blocks) int32 page table mapping
        each stream's logical blocks to pool blocks. Layers without a KV
        cache ignore the table."""
        return self.decode_step(params, dstate, x, pos, state=state)

    def prefill_chunk(self, params, dstate, x, start, n, state=None,
                      block_tables=None, carry_stack=False):
        """Advance a chunk of prefill positions in one call. ``x``:
        (B, K, F) activations for positions ``start .. start+K-1`` per
        stream; ``n``: (B,) int32 valid rows (rows t >= n[b] are padding —
        their state writes are masked and their outputs garbage the caller
        discards). Returns ``(y, new_dstate)`` with y (B, K, F_out).

        Default: stateless layers apply() the whole chunk (timestep-wise
        ops make this the full-forward math); stateful layers advance
        their carry by scanning ``decode_step`` with a per-row valid mask
        — bitwise the same trajectory a token-at-a-time prefill walks.

        ``carry_stack=True`` returns ``(y, new_dstate, snapshots)`` where
        ``snapshots`` stacks the carry after EVERY chunk position along a
        leading (K, ...) axis (None for stateless layers and layers whose
        state is positional — ``positional_state_keys``). The speculative
        verify program (serving/spec/verify.py) rewinds a slot to the
        carry after its accepted prefix by selecting into this stack."""
        if dstate is None:
            y, _ = self.apply(params, x, state, train=False, rng=None)
            return (y, dstate, None) if carry_stack else (y, dstate)
        B, K = x.shape[0], x.shape[1]
        xs = jnp.moveaxis(x, 1, 0)[:, :, None, :]       # (K, B, 1, F)

        def step(d, xt_t):
            xt, t = xt_t
            y, nd = self.decode_step(params, d, xt, start + t, state=state)
            v = t < n                                   # (B,) row validity

            def keep(a, b):
                return jnp.where(v.reshape((B,) + (1,) * (a.ndim - 1)), a, b)

            nd = jax.tree_util.tree_map(keep, nd, d)
            return nd, ((y, nd) if carry_stack else y)

        if carry_stack:
            d, (ys, snaps) = jax.lax.scan(step, dstate, (xs, jnp.arange(K)))
            return jnp.moveaxis(ys[:, :, 0, :], 0, 1), d, snaps
        d, ys = jax.lax.scan(step, dstate, (xs, jnp.arange(K)))
        return jnp.moveaxis(ys[:, :, 0, :], 0, 1), d

    # ---- tree-speculation protocol (serving/spec/tree.py) ----------------
    # Tree verification feeds N tree NODES as extra window positions:
    # node n sits at stream position ``pos0 + tree.depth[n]`` and may only
    # see its own root-path (ancestry, not linearity). Stateless layers
    # are position-free and just apply(); carry layers scan the nodes with
    # a node-indexed snapshot stack so every node resumes its PARENT's
    # carry; attention overrides with an ancestry-masked cache read that
    # writes NOTHING (siblings share stream positions, so committing
    # before acceptance would collide) — the winning path's KV lands in
    # ``tree_commit`` afterwards, inside the same verify program.
    def tree_chunk(self, params, dstate, x, pos0, tree, n, state=None,
                   block_tables=None):
        """Score all N tree nodes in one call. ``x``: (B, N, F) node
        activations in tree order; ``pos0``: (B,) root stream position;
        ``tree``: the static ``serving.spec.tree.TreeSpec``; ``n``: (B,)
        emit budget (0 = inert row, its state must stay bitwise).

        Returns ``(y, new_dstate, carry_stack, kv_window)``:

        - ``y`` (B, N, F_out) per-node outputs,
        - ``new_dstate`` — positional leaves unchanged (nothing is
          committed here), carry leaves unchanged (the verifier selects
          the final carry out of the stack),
        - ``carry_stack`` — carries stacked along a leading NODE axis
          (N, B, ...): entry n is the carry after node n's root-path,
          so rewind is ``stack[path_node, rows]`` (None when the layer
          keeps no carry),
        - ``kv_window`` — the N nodes' fresh K/V rows for
          ``tree_commit`` (attention only, else None)."""
        if dstate is None:
            y, _ = self.apply(params, x, state, train=False, rng=None)
            return y, dstate, None, None
        B, N = x.shape[0], x.shape[1]
        xs = jnp.moveaxis(x, 1, 0)[:, :, None, :]       # (N, B, 1, F)
        parent = jnp.asarray(tree.parent, jnp.int32)
        depth = jnp.asarray(tree.depth, jnp.int32)
        tmap = jax.tree_util.tree_map
        stack0 = tmap(lambda a: jnp.zeros((N,) + a.shape, a.dtype), dstate)

        def step(stack, xt_t):
            xt, t = xt_t
            par = parent[t]
            # resume the PARENT's carry: the root (par < 0) resumes the
            # slot's incoming carry, every other node its parent snapshot
            d_in = tmap(
                lambda s, base: jnp.where(par < 0, base,
                                          s[jnp.clip(par, 0, N - 1)]),
                stack, dstate)
            y, nd = self.decode_step(params, d_in, xt, pos0 + depth[t],
                                     state=state)
            stack = tmap(lambda s, a: s.at[t].set(a), stack, nd)
            return stack, y

        stack, ys = jax.lax.scan(step, stack0, (xs, jnp.arange(N)))
        return jnp.moveaxis(ys[:, :, 0, :], 0, 1), dstate, stack, None

    def tree_commit(self, params, dstate, kv_window, path, pos0, commit_n,
                    block_tables=None):
        """Write the accepted root-path's positional state. ``path``:
        (B, D+1) accepted node index per depth (saturated past the
        accepted depth); ``commit_n``: (B,) number of depths to commit
        (= emitted tokens; 0 = inert row, state bitwise untouched).
        Only layers with positional state override; the default is a
        no-op because carry layers roll back through the snapshot stack
        instead (serving/spec/rewind.py)."""
        return dstate

    def has_params(self) -> bool:
        return True

    # dropout on the INPUT activations, matching the reference convention
    # (BaseLayer.applyDropOutIfNecessary before preOutput). ``dropout`` is a
    # float drop-probability (standard dropout) or an IDropout object
    # (AlphaDropout/GaussianDropout/GaussianNoise — nn/conf/dropout parity)
    def maybe_dropout(self, x, *, train, rng):
        d = self.dropout
        if not train or d is None or rng is None:
            return x
        from deeplearning4j_tpu.nn.dropout import IDropout
        if isinstance(d, IDropout):
            return d.apply(x, rng)
        if d <= 0.0:
            return x
        keep = 1.0 - d
        m = jax.random.bernoulli(rng, keep, x.shape)
        return jnp.where(m, x / keep, 0.0)

    # ---- the layer's own loss term and regularization: the container sums
    # ---- these into the loss
    def loss_term(self, new_state):
        """The scalar under ``loss_state`` of the state this layer's
        training-mode ``apply`` returned, or 0.0."""
        if self.loss_state and new_state:
            return new_state[self.loss_state]
        return 0.0

    def reg_loss(self, params):
        l1 = self.l1 or 0.0
        l2 = self.l2 or 0.0
        if (l1 == 0.0 and l2 == 0.0) or not params:
            return 0.0
        total = 0.0
        for k, v in params.items():
            if k.startswith("b") or k in ("beta", "gamma", "mean", "var"):
                continue  # no l1/l2 on biases or norm params, like the reference
            for vv in jax.tree_util.tree_leaves(v):
                total = total + l1 * jnp.abs(vv).sum() + 0.5 * l2 * (vv ** 2).sum()
        return total

    def apply_constraints(self, params):
        """Post-update parameter constraints (parity: nn/conf/constraint/*)."""
        if not self.constraints or not params:
            return params
        kind = self.constraints[0]
        arg = self.constraints[1] if len(self.constraints) > 1 else 1.0
        out = dict(params)
        for k, v in params.items():
            if k.startswith("b") or isinstance(v, dict):
                continue
            if kind == "maxnorm":
                axes = tuple(range(v.ndim - 1))
                n = jnp.sqrt((v ** 2).sum(axis=axes, keepdims=True))
                out[k] = v * jnp.clip(n, 0, arg) / jnp.maximum(n, 1e-8)
            elif kind == "unitnorm":
                axes = tuple(range(v.ndim - 1))
                n = jnp.sqrt((v ** 2).sum(axis=axes, keepdims=True))
                out[k] = v / jnp.maximum(n, 1e-8)
            elif kind == "nonneg":
                out[k] = jnp.maximum(v, 0.0)
            elif kind == "minmaxnorm":
                lo, hi = self.constraints[1], self.constraints[2]
                axes = tuple(range(v.ndim - 1))
                n = jnp.sqrt((v ** 2).sum(axis=axes, keepdims=True))
                out[k] = v * jnp.clip(n, lo, hi) / jnp.maximum(n, 1e-8)
        return out

    # ---- serde -----------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        from deeplearning4j_tpu.nn.weightnoise import IWeightNoise
        from deeplearning4j_tpu.nn.dropout import IDropout
        d = {}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if isinstance(v, Updater):
                v = v.to_dict()
            elif isinstance(v, (IWeightNoise, IDropout)):
                v = v.to_dict()
            elif isinstance(v, Layer):  # wrappers (Bidirectional, Frozen)
                v = v.to_dict()
            elif isinstance(v, tuple):
                v = list(v)
            d[f.name] = v
        d["@type"] = type(self).__name__
        return d

    @classmethod
    def _from_dict_fields(cls, d):
        d = dict(d)
        d.pop("@type", None)
        fields = {f.name: f for f in dataclasses.fields(cls)}
        kwargs = {}
        for k, v in d.items():
            if k not in fields:
                continue
            if k == "updater" and isinstance(v, dict):
                v = Updater.from_dict(v)
            elif isinstance(v, dict) and "@noise" in v:
                from deeplearning4j_tpu.nn.weightnoise import IWeightNoise
                v = IWeightNoise.from_dict(v)
            elif isinstance(v, dict) and "@dropout" in v:
                from deeplearning4j_tpu.nn.dropout import IDropout
                v = IDropout.from_dict(v)
            elif isinstance(v, dict) and "@type" in v:
                v = layer_from_dict(v)
            elif isinstance(v, list):
                v = tuple(v)
            kwargs[k] = v
        return cls(**kwargs)


def layer_from_dict(d: Dict[str, Any]) -> Layer:
    cls = LAYER_REGISTRY[d["@type"]]
    return cls._from_dict_fields(d)


def require_dims(layer, **dims):
    """Validate that inferred/declared dims are set before init — catches
    building a net without set_input_type and without explicit n_in."""
    for k, v in dims.items():
        if not v or v <= 0:
            raise ValueError(
                f"{type(layer).__name__}: {k}={v} is not set. Provide "
                f"set_input_type(...) on the ListBuilder/GraphBuilder or set "
                f"{k} explicitly on the layer.")


def as_pair(v):
    """Normalize an int-or-pair hyperparameter to a 2-tuple."""
    if isinstance(v, (tuple, list)):
        return tuple(v)
    return (v, v)
