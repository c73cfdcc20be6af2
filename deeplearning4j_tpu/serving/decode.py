"""Incremental decoding engine: stateful step caches + slot-based
continuous batching for autoregressive serving.

``InferenceEngine`` (engine.py) amortizes compiles across request SHAPES;
this module amortizes the autoregressive loop across concurrent REQUESTS.
A naive text-generation server re-runs the full prefix forward for every
token (O(T²) work per sequence) and batches only at request granularity —
a long sequence blocks the batch until it finishes. Here, decode state
(LSTM (h, c) carries, attention KV caches) stays resident on device in ONE
batched tree of S slots, and the server batches at ITERATION granularity
(the Orca/vLLM scheduling model): every device call advances all active
sequences by one token, new requests claim free slots mid-flight, finished
sequences free their slot without touching the compiled program.

Design rules the tests pin:

- ONE compiled program. Every step runs the same (S,)-shaped jitted
  function (donated state buffers), regardless of which slots are active,
  how requests arrive, or when they finish. ``trace_count`` counts XLA
  programs exactly, engine.py-style.
- Bitwise parity. A token decoded incrementally is bitwise-equal to the
  same position of a teacher-forced full-prefix forward (layer contract in
  nn/layers/base.py ``decode_step``; see docs/DECODING.md for the XLA:CPU
  fusion subtleties this requires).
- No state leakage. A freed slot's state is wiped INSIDE the step (reset
  mask) when re-claimed, so slot reuse can never see a previous request's
  carries; inactive slots are frozen by an active mask (their state is
  bit-identical across steps they don't participate in).
- Deterministic sampling. The PRNG key for a token is
  ``fold_in(PRNGKey(request_seed), position)`` — a pure function of the
  request, never of the slot index or co-tenants — so any arrival
  schedule produces the same text for the same seed.
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from concurrent.futures import Future
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu.monitor import compile_ledger, get_registry, trace
from deeplearning4j_tpu.monitor.reqlog import RequestLog, new_record
from deeplearning4j_tpu.monitor.tracing import get_context
from deeplearning4j_tpu.resilience.errors import (
    BatcherStoppedError, ServerOverloadedError)
from deeplearning4j_tpu.quant import (dequantize_tree, record_weight_bytes,
                                      resolve_precision, tree_bytes)
from deeplearning4j_tpu.serving.engine import (_tree_signature,
                                               _validate_sig, validate_swap)
from deeplearning4j_tpu.serving.kv import (BlockPool, PoolExhaustedError,
                                           PrefixCache, blocks_for_span,
                                           map_pool_leaves, map_slot_leaves)
from deeplearning4j_tpu.serving.spec.accept import oracle_token, oracle_tokens
from deeplearning4j_tpu.serving.spec.draft import DraftEngine
from deeplearning4j_tpu.serving.spec.verify import SpecVerifier


class _Request:
    """Host-side bookkeeping for one occupied slot."""

    __slots__ = ("prompt", "max_new", "seed", "temperature", "top_k",
                 "cursor", "generated", "future", "fresh", "t_start",
                 "kv_blocks", "draft_cursor", "draft_sel", "draft_fresh",
                 "rid", "tenant", "priority", "trace_id",
                 "t_admit", "t_prefill0", "t_first", "t_last",
                 "verify_s", "drafted", "accepted",
                 "prefix_hit", "host_restores")

    def __init__(self, prompt, max_new, seed, temperature, top_k, future,
                 rid=None, tenant="default", priority="normal",
                 trace_id=None):
        self.prompt = list(prompt)
        self.max_new = int(max_new)
        self.seed = int(seed)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.cursor = 0          # next input position to feed
        self.generated: List[int] = []
        self.future = future
        self.fresh = True        # first step must wipe the slot's state
        self.t_start = time.perf_counter()
        self.kv_blocks: List[int] = []   # paged engines: claimed pool blocks
        # speculative engines: the draft model's own progress through this
        # stream (it prefills the prompt independently of the target)
        self.draft_cursor = 0    # next input position the DRAFT will feed
        self.draft_sel = 0       # snapshot stack index to resume carries at
        self.draft_fresh = True  # first draft call must wipe the draft slot
        # request-lifecycle identity + host-side perf_counter stamps (the
        # wide-event record, docs/OBSERVABILITY.md "Request lifecycle").
        # Every stamp rides an existing host-side point in the tick loop
        # — the instrumentation adds ZERO device syncs.
        self.rid = rid
        self.tenant = tenant
        self.priority = priority
        self.trace_id = trace_id
        self.t_admit = None      # slot claimed (queue phase ends)
        self.t_prefill0 = None   # first prefill work dispatched
        self.t_first = None      # first token emitted (TTFT)
        self.t_last = None       # latest emission run (ITL reference)
        self.verify_s = 0.0      # spec: wall spent in verify calls
        self.drafted = 0         # spec: tokens proposed for this stream
        self.accepted = 0        # spec: tokens accepted for this stream
        self.prefix_hit = 0      # paged: prompt positions reused from cache
        self.host_restores = 0   # paged: host-tier blocks promoted for us


class DecodeEngine:
    """Continuous-batching autoregressive decoder over a model container.

    ``model`` is a MultiLayerNetwork or ComputationGraph whose layers
    implement the incremental-decode protocol (``init_decode_state`` /
    ``decode_step``) and whose output layer emits per-token probabilities
    (e.g. RnnOutputLayer softmax). Inputs are token ids; the engine
    one-hots them on device to the model's input width.

        eng = DecodeEngine(net, slots=32, max_len=256).start()
        toks = eng.generate([3, 1, 4], max_new_tokens=64)["tokens"]

    ``slots``: concurrent streams held in the batched state tree.
    ``max_len``: fixed KV-cache capacity = max prompt+generated length.
    ``eos_id``: token id that finishes a stream early (None = length only).
    ``max_queue``: bound on waiting requests (beyond it: overload error,
    HTTP 429 through the server).
    ``kv``: ``"dense"`` (per-slot contiguous caches, the default) or
    ``"paged"`` (device-resident block pool + per-slot page tables —
    docs/DECODING.md "Paged KV cache"). Paged engines accept
    ``kv_block_size`` (tokens per block), ``kv_blocks`` (pool size; default
    sizes the pool for full occupancy), ``prefix_cache`` (reuse completed
    prefill blocks across requests sharing a prompt prefix; requires a
    model with no recurrent per-slot decode state) and ``chunk_tokens``
    (split prefill into chunks of this many tokens that ride the batched
    iteration cadence next to live decode slots, instead of occupying one
    decode step per prompt token).
    ``spec``: a ``serving.spec.SpecConfig`` switches the scheduler to
    speculative decoding — a draft (a separate model, or the target
    itself via ``self_draft``) proposes a token TREE per tick
    (``tree=(k_1,..,k_D)``; plain ``k`` = the linear chain) and the
    target verifies every node in one batched step, emitting 1..D+1
    tokens per tick while staying bitwise-identical to the
    non-speculative engine (docs/DECODING.md "Tree speculation &
    self-drafting").
    """

    _ids = itertools.count()

    def __init__(self, model, slots: int = 8, max_len: int = 256,
                 eos_id: Optional[int] = None, max_queue: int = 256,
                 precision: Optional[str] = None, kv: str = "dense",
                 kv_block_size: int = 16, kv_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 chunk_tokens: Optional[int] = None,
                 host_kv_bytes: Optional[int] = None,
                 spec=None, journal_capacity: int = 512):
        self.model = model
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.max_queue = int(max_queue)
        if kv not in ("dense", "paged"):
            raise ValueError(f"kv must be 'dense' or 'paged', got {kv!r}")
        if kv == "dense" and chunk_tokens is not None:
            raise ValueError("chunk_tokens requires kv='paged'")
        if kv == "paged" and self.max_len % int(kv_block_size) != 0:
            # the gathered paged cache must cover exactly max_len positions
            # for bitwise parity with the dense step program
            raise ValueError(
                f"max_len ({max_len}) must be a multiple of kv_block_size "
                f"({kv_block_size})")
        if chunk_tokens is not None and int(chunk_tokens) < 1:
            raise ValueError("chunk_tokens must be >= 1")
        if host_kv_bytes is not None and (
                kv != "paged" or not prefix_cache):
            raise ValueError(
                "host_kv_bytes requires kv='paged' with prefix_cache=True "
                "(the tier holds evicted prefix-cache blocks)")
        self.kv = kv
        self.kv_block_size = int(kv_block_size)
        self.chunk_tokens = (int(chunk_tokens) if chunk_tokens is not None
                             else None)
        self.kv_max_blocks = (self.max_len // self.kv_block_size
                              if kv == "paged" else 0)
        self._pool: Optional[BlockPool] = None
        self._prefix: Optional[PrefixCache] = None
        self._tables: Optional[np.ndarray] = None
        self._pending_cows: List[tuple] = []
        self._host_tier = None
        # bid -> per-leaf host rows: tier restores claimed during match
        # whose host→device scatter is still pending (applied in one
        # batch before the next device call, like _pending_cows)
        self._pending_restores: dict = {}
        # export/import closures marshalled onto the loop thread — the
        # only thread allowed to touch the donated decode state
        self._kv_ops: deque = deque()
        self._kv_blocked = False
        self._is_graph = hasattr(model.conf, "network_inputs")
        itype = (model.conf.input_types[0] if self._is_graph
                 else model.conf.input_type)
        self.vocab = itype.size
        self.warmup_seconds: Optional[float] = None
        self._spec = spec
        if spec is not None:
            from deeplearning4j_tpu.serving.spec import TreeSpec
            from deeplearning4j_tpu.serving.spec.selfdraft import \
                build_self_draft
            if int(spec.k) < 1:
                raise ValueError(f"spec.k must be >= 1, got {spec.k}")
            # static tree shape: SpecConfig.tree or the linear (1,)*k
            self._spec_tree = TreeSpec(spec.kvec())
            # draft scan width: spine depth + 1 snapshot slack (the extra
            # position keeps a resume snapshot live at full acceptance)
            self._spec_k = self._spec_tree.d + 1
            dm = spec.draft_model
            if (dm is None) == (spec.self_draft is None):
                raise ValueError(
                    "spec needs exactly one of draft_model or self_draft "
                    f"(got draft_model={dm!r}, "
                    f"self_draft={spec.self_draft!r})")
            if spec.self_draft is not None:
                dm, self._spec_draft_precision = build_self_draft(
                    model, spec)
            else:
                # the draft proposes TOKEN IDS the target verifies — only
                # meaningful over the exact same vocabulary
                ditype = (dm.conf.input_types[0]
                          if hasattr(dm.conf, "network_inputs")
                          else dm.conf.input_type)
                if ditype.size != self.vocab:
                    raise ValueError(
                        f"draft model vocabulary ({ditype.size}) must "
                        f"match the target's ({self.vocab})")
                self._spec_draft_precision = spec.draft_precision
            self._spec_draft_model = dm

        from deeplearning4j_tpu import exec as ex
        execu = getattr(model, "_executor", None) or ex.get_executor()
        # serving precision (engine.py policy, docs/QUANTIZATION.md):
        # int8/fp8 pins the quantized weights now and keeps the f32
        # signature so staged swaps validate f32 candidates and quantize
        # AFTER the gate — the one step program never re-traces
        self.precision = (resolve_precision(precision)
                          if precision is not None else execu.precision)
        self._raw_sig = None
        if self.kv == "paged":
            # same step program shape every call: the (S, max_blocks) page
            # table rides in as one more (S,)-leading data argument
            self._step = execu.jit(
                self._step_impl_paged,
                in_specs=(ex.PARAMS, ex.STATE, ex.SLOTS, ex.BATCH, ex.BATCH,
                          ex.BATCH, ex.BATCH, ex.BATCH, ex.BATCH, ex.BATCH,
                          ex.BATCH),
                out_specs=(ex.BATCH, ex.SLOTS),
                donate_argnums=(2,))
        else:
            self._step = execu.jit(
                self._step_impl,
                in_specs=(ex.PARAMS, ex.STATE, ex.SLOTS, ex.BATCH, ex.BATCH,
                          ex.BATCH, ex.BATCH, ex.BATCH, ex.BATCH, ex.BATCH),
                out_specs=(ex.BATCH, ex.SLOTS),
                donate_argnums=(2,))
        self._prefill = None
        self._cow = None
        if self.chunk_tokens is not None:
            self._prefill = execu.jit(
                self._prefill_impl,
                in_specs=(ex.PARAMS, ex.STATE, ex.SLOTS, ex.BATCH, ex.BATCH,
                          ex.BATCH, ex.BATCH, ex.BATCH),
                out_specs=(ex.SLOTS,),
                donate_argnums=(2,))
        if self.kv == "paged" and prefix_cache:
            self._cow = execu.jit(
                self._cow_impl,
                in_specs=(ex.SLOTS, ex.REPL, ex.REPL),
                out_specs=(ex.SLOTS,),
                donate_argnums=(0,))
        self._dstate = None
        self._live = None          # (params, state) after the first swap
        if self.precision != "f32":
            self._raw_sig = _tree_signature(model.params)
            qp = execu.prepare_params(model.params, self.precision)
            st = jax.tree_util.tree_map(jnp.asarray, model.state)
            self._live = (qp, st)
        self._pending_swap = None  # staged (params, state, version, Event)
        self._version = 0
        self._slot_reqs: List[Optional[_Request]] = [None] * self.slots
        self._queue: deque = deque()
        self._cv = threading.Condition()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._decode_seconds = 0.0

        self.id = f"decode{next(DecodeEngine._ids)}"
        reg = get_registry()
        lab = {"engine": self.id}
        self._m_compiled = reg.counter(
            "dl4jtpu_decode_compiled_programs_total",
            "XLA programs traced for the batched decode step (design "
            "target: exactly one per model).", ("engine",)).labels(**lab)
        self._m_steps = reg.counter(
            "dl4jtpu_decode_steps_total",
            "Batched decode-step device calls.", ("engine",)).labels(**lab)
        self._m_tokens = reg.counter(
            "dl4jtpu_decode_tokens_total",
            "Tokens generated (sampled outputs only — prefill positions "
            "are not counted).", ("engine",)).labels(**lab)
        self._m_requests = reg.counter(
            "dl4jtpu_decode_requests_total",
            "Generation requests completed.", ("engine",)).labels(**lab)
        self._m_occupancy = reg.gauge(
            "dl4jtpu_decode_active_slots",
            "Slots occupied by live streams at the last step.",
            ("engine",)).labels(**lab)
        self._m_token_seconds = reg.histogram(
            "dl4jtpu_decode_token_seconds",
            "Per-token latency: wall seconds of one batched step (every "
            "active stream advances one token per step).",
            ("engine",)).labels(**lab)
        # request-lifecycle SLO histograms (docs/OBSERVABILITY.md
        # "Request lifecycle"): fed from host-side perf_counter stamps at
        # existing emission points — zero device syncs added to the tick
        # loop. Observations carry the request id as a bucket exemplar.
        self._m_ttft = reg.histogram(
            "dl4jtpu_decode_ttft_seconds",
            "Time-to-first-token: submit to first emitted token, queue "
            "wait included (the prefill-dominated serving SLO).",
            ("engine",)).labels(**lab)
        self._m_itl = reg.histogram(
            "dl4jtpu_decode_itl_seconds",
            "Inter-token latency: wall between consecutive emitted "
            "tokens; speculative runs contribute one sample per accepted "
            "token (run wall / run length).", ("engine",)).labels(**lab)
        self._m_queue = reg.histogram(
            "dl4jtpu_decode_queue_seconds",
            "Admission queue wait: submit to slot claim.",
            ("engine",)).labels(**lab)
        # the wide-event request journal (terminal record per request,
        # completions AND rejections) served at GET /requests
        self.journal = RequestLog(journal_capacity)
        self._m_version = reg.gauge(
            "dl4jtpu_model_version",
            "Version of the weights currently serving (0 = the model's "
            "initial weights; bumped by every hot swap).",
            ("engine",)).labels(**lab)
        self._m_swaps = reg.counter(
            "dl4jtpu_model_swaps_total",
            "Weight hot-swaps applied with zero new XLA compiles.",
            ("engine",)).labels(**lab)
        self._m_version.set(0.0)
        if self.precision != "f32":
            record_weight_bytes(self.id, self.precision,
                                tree_bytes(self._live[0]))

        if self.kv == "paged":
            if kv_blocks is None:
                # full occupancy by default: every slot can hold max_len
                # tokens, +1 for the reserved scratch block
                kv_blocks = self.slots * self.kv_max_blocks + 1
            self._pool = BlockPool(int(kv_blocks), self.kv_block_size,
                                   engine=self.id)
            self._tables = np.zeros((self.slots, self.kv_max_blocks),
                                    np.int32)
            if prefix_cache:
                # prefix reuse assumes a slot's KV blocks are the ONLY
                # per-slot decode state — recurrent carries (LSTM h/c)
                # depend on every earlier token and cannot be shared.
                probe = self.model.init_decode_state(
                    1, self.max_len,
                    kv={"num_blocks": 2, "block_size": self.kv_block_size})
                from deeplearning4j_tpu.serving.kv import is_pool_path
                carries = []
                jax.tree_util.tree_map_with_path(
                    lambda p, a: carries.append(p)
                    if not is_pool_path(p) else None, probe)
                if carries:
                    raise ValueError(
                        "prefix_cache=True requires a model whose only "
                        "per-slot decode state is the paged KV cache; this "
                        "model carries recurrent state "
                        f"({len(carries)} non-pool leaves). Pass "
                        "prefix_cache=False.")
                self._prefix = PrefixCache(self._pool)
                if host_kv_bytes is not None:
                    from deeplearning4j_tpu.serving.kv import HostKVTier
                    self._host_tier = HostKVTier(int(host_kv_bytes),
                                                 engine=self.id)
                    self._prefix.tier = self._host_tier
                    self._prefix.spill_fn = self._spill_block
                    self._prefix.restore_fn = self._restore_block
            self._m_kv_programs = reg.counter(
                "dl4jtpu_kv_compiled_programs_total",
                "XLA programs traced for the paged-KV side programs "
                "(chunked prefill + copy-on-write; design target: at most "
                "one each).", ("engine",)).labels(**lab)
            self._m_kv_exhausted = reg.counter(
                "dl4jtpu_kv_pool_exhausted_total",
                "Admissions stalled because the KV block pool could not "
                "cover the request at the queue head.",
                ("engine",)).labels(**lab)
            self._m_prefix_hits = reg.counter(
                "dl4jtpu_kv_prefix_hits_total",
                "Requests that reused at least one cached prefix block.",
                ("engine",)).labels(**lab)
            self._m_prefix_saved = reg.counter(
                "dl4jtpu_kv_prefix_tokens_saved_total",
                "Prefill positions skipped by prefix-cache reuse.",
                ("engine",)).labels(**lab)
            self._m_cow = reg.counter(
                "dl4jtpu_kv_cow_copies_total",
                "Copy-on-write block copies (partial prefix match claimed "
                "then diverged into a private block).",
                ("engine",)).labels(**lab)
            self._m_prefill_chunks = reg.counter(
                "dl4jtpu_kv_prefill_chunks_total",
                "Chunked-prefill slot-chunks executed.",
                ("engine",)).labels(**lab)
            self._m_prefill_tokens = reg.counter(
                "dl4jtpu_kv_prefill_tokens_total",
                "Prompt tokens prefilled through the chunked-prefill "
                "program.", ("engine",)).labels(**lab)
            self._m_host_restores = reg.counter(
                "dl4jtpu_kv_host_restores_total",
                "Spilled prefix blocks promoted back from the host tier "
                "on a second-chance match hit.", ("engine",)).labels(**lab)
            self._m_migrate_exports = reg.counter(
                "dl4jtpu_kv_migrate_exports_total",
                "Block chains serialized for replica-to-replica KV "
                "migration (/kv/export).", ("engine",)).labels(**lab)
            self._m_migrate_imports = reg.counter(
                "dl4jtpu_kv_migrate_imports_total",
                "Block chains restored from a migration payload "
                "(/kv/import).", ("engine",)).labels(**lab)
            self._m_migrate_rejects = reg.counter(
                "dl4jtpu_kv_migrate_rejects_total",
                "Migration payloads rejected before touching the pool "
                "(envelope mismatch, torn bytes, exhausted destination).",
                ("engine", "reason"))

        self._verifier = None
        self._draft = None
        if spec is not None:
            self._verifier = SpecVerifier(
                self.model, self.id, self.slots, self.max_len,
                self._spec_tree, self.vocab, kv=self.kv,
                kv_max_blocks=self.kv_max_blocks)
            self._draft = DraftEngine(
                self._spec_draft_model, self.id, self.slots, self.max_len,
                self._spec_k, self.vocab,
                precision=self._spec_draft_precision,
                side_k=max(self._spec_tree.kvec) - 1)
            self._m_spec_drafted = reg.counter(
                "dl4jtpu_spec_drafted_tokens_total",
                "Tokens proposed by the speculative draft model.",
                ("engine",)).labels(**lab)
            self._m_spec_accepted = reg.counter(
                "dl4jtpu_spec_accepted_tokens_total",
                "Drafted tokens accepted by target verification "
                "(exact-match against the sampling oracle).",
                ("engine",)).labels(**lab)
            self._m_spec_rate = reg.gauge(
                "dl4jtpu_spec_acceptance_rate",
                "Lifetime accepted/drafted ratio — the draft-quality "
                "signal that decides whether speculation pays.",
                ("engine",)).labels(**lab)
            self._m_spec_draft_seconds = reg.histogram(
                "dl4jtpu_spec_draft_step_seconds",
                "Wall seconds of one k-token draft-model call (compare "
                "against dl4jtpu_decode_token_seconds: speculation wins "
                "while draft cost + one verify < k target steps).",
                ("engine",)).labels(**lab)
            self._m_spec_depth = reg.histogram(
                "dl4jtpu_spec_accepted_depth",
                "Accepted tree depth per verify (0 = root correction "
                "only): the distribution behind the acceptance-rate "
                "gauge — a mass pile-up at 0 means the tree's depth "
                "budget is wasted.",
                ("engine",),
                buckets=tuple(float(d)
                              for d in range(self._spec_tree.d + 1))
            ).labels(**lab)
            self._m_spec_nodes = reg.gauge(
                "dl4jtpu_spec_tree_nodes",
                "Static speculation-tree size (nodes scored per verify "
                "call) — the verify-cost side of the tree-shape "
                "trade-off.", ("engine",)).labels(**lab)
            self._m_spec_nodes.set(float(self._spec_tree.n_nodes))

    @property
    def trace_count(self) -> int:
        return int(self._m_compiled.value)

    @property
    def model_version(self) -> int:
        return self._version

    def _weights(self):
        """Live (params, state): the engine's own pair after a swap was
        applied, the model's until then (so a freshly built engine still
        follows further ``fit()`` calls on its model)."""
        live = self._live
        if live is not None:
            return live
        return self.model.params, self.model.state

    def swap_weights(self, params, state=None, version: Optional[int] = None,
                     timeout: Optional[float] = 60.0) -> int:
        """Stage a same-shape weight swap and wait for it to apply.

        Continuous batching means slots from different requests share every
        device call, and a generation must run END-TO-END on one model
        version — so the swap is deferred: admission pauses, in-flight
        generations finish on the old weights (bounded by their remaining
        ``max_new_tokens``), and the loop applies the swap at the first
        step boundary with zero live slots, then re-admits. The candidate
        is validated BEFORE staging (``WeightSwapError`` leaves the engine
        untouched), and identical shapes/dtypes mean the single compiled
        step program is reused — zero new XLA compiles."""
        cur_p, cur_s = self._weights()
        if self._raw_sig is not None:
            _validate_sig(self._raw_sig, _tree_signature(params),
                          "decode params")
        else:
            validate_swap(cur_p, params, "decode params")
        if state is not None:
            validate_swap(cur_s, state, "decode state")
        params = jax.tree_util.tree_map(jnp.asarray, params)
        if self.precision != "f32":
            from deeplearning4j_tpu import exec as ex
            execu = getattr(self.model, "_executor", None) \
                or ex.get_executor()
            params = execu.prepare_params(params, self.precision)
            record_weight_bytes(self.id, self.precision, tree_bytes(params))
        state = (cur_s if state is None
                 else jax.tree_util.tree_map(jnp.asarray, state))
        applied = threading.Event()
        with self._cv:
            self._pending_swap = (params, state, version, applied)
            self._cv.notify_all()
            if self._thread is None or not self._thread.is_alive():
                self._apply_swap_locked()   # no loop running: apply now
        if timeout is not None and not applied.wait(timeout):
            raise TimeoutError(
                f"decode weight swap not applied within {timeout}s "
                f"(in-flight generations still draining)")
        return self._version

    def _apply_swap_locked(self) -> None:
        """Apply the staged swap (caller holds ``self._cv``, no live
        slots)."""
        params, state, version, applied = self._pending_swap
        self._pending_swap = None
        self._live = (params, state)
        if self._prefix is not None:
            # cached KV was computed under the OLD weights — reusing it
            # across a swap would splice two model versions into one stream
            self._prefix.clear()
        self._version = (int(version) if version is not None
                         else self._version + 1)
        self._m_version.set(float(self._version))
        self._m_swaps.inc()
        applied.set()

    @property
    def saturated(self) -> bool:
        """All S slots busy: a new /generate would queue behind a full
        batch. /healthz reports ``degraded`` in this state so a router can
        steer prefill-heavy work to replicas with free slots."""
        with self._cv:
            return (self.slots > 0
                    and all(r is not None for r in self._slot_reqs))

    @property
    def kv_exhausted(self) -> bool:
        """Paged engines: the request at the queue head could not claim
        blocks at the last admission pass (clears as blocks release).
        /healthz reports ``degraded`` with the pool occupancy."""
        if self._pool is None:
            return False
        with self._cv:
            return self._kv_blocked

    def kv_pool_info(self) -> Optional[dict]:
        """Pool occupancy snapshot for /healthz and stats (None = dense)."""
        if self._pool is None:
            return None
        info = {"blocks": self._pool.usable,
                "blocks_free": self._pool.free_count,
                "blocks_in_use": self._pool.in_use,
                "blocks_cached": self._pool.cached_count,
                "block_size": self.kv_block_size,
                "high_water": self._pool.high_water}
        if self._host_tier is not None:
            info["host_tier"] = self._host_tier.stats()
        return info

    # ------------------------------------------------------------- the step
    def _step_impl(self, params, state, dstate, tokens, pos, reset, active,
                   seeds, temps, topk, btab=None):
        """ONE iteration for all S slots. All arguments are (S,)-shaped, so
        every call shares a single XLA program; scheduling decisions ride in
        as data (masks), never as shapes. ``btab`` (paged engines) is the
        (S, max_blocks) page table — also data, same program shape."""
        from deeplearning4j_tpu.exec.programs import is_registering
        if not is_registering():
            self._m_compiled.inc()   # traced-only: exact compiled-program count
        # dequant-on-the-fly (identity on the f32 path): int8/fp8 weights
        # stream from HBM at quantized width every step — the decode step
        # is weight-bandwidth-bound, so this is where low precision pays
        params = dequantize_tree(params)
        S = self.slots

        def wipe(a):
            r = reset.reshape((S,) + (1,) * (a.ndim - 1))
            return jnp.where(r, jnp.zeros_like(a), a)

        # re-claimed slots start from zero state INSIDE the step — claiming
        # a slot never needs a second program, and stale carries can't leak.
        # Paged engines never wipe the pool: blocks are recycled by the
        # host-side refcounts, and a reset slot's table points at fresh ones.
        tmap = (jax.tree_util.tree_map if btab is None else map_slot_leaves)
        dstate = tmap(wipe, dstate)
        x = jax.nn.one_hot(tokens, self.vocab, dtype=jnp.float32)[:, None, :]
        if btab is None:
            y, new_d = self.model.decode_step(params, state, dstate, x, pos)
        else:
            y, new_d = self.model.decode_step(params, state, dstate, x, pos,
                                              block_tables=btab)

        # ONE sampling rule for the whole codebase: generate_naive and the
        # speculative verify program (serving/spec/) call the same oracle,
        # so every path emits bitwise-identical tokens for the same
        # (distribution, seed, position). log(probs) is monotone, so
        # top-k filtering and argmax are equivalent on either scale.
        next_tok = oracle_tokens(jnp.log(y[:, 0, :]), seeds, pos, temps, topk)
        next_tok = jnp.where(active, next_tok, 0)

        def freeze(new, old):
            a = active.reshape((S,) + (1,) * (new.ndim - 1))
            return jnp.where(a, new, old)

        # inactive slots keep their state bit-identical (numerically inert)
        new_d = tmap(freeze, new_d, dstate)
        return next_tok, new_d

    def _step_impl_paged(self, params, state, dstate, btab, tokens, pos,
                         reset, active, seeds, temps, topk):
        """Paged step: the page table is a positional arg (donation-friendly
        ordering: state right after params/state, (S,)-data after)."""
        return self._step_impl(params, state, dstate, tokens, pos, reset,
                               active, seeds, temps, topk, btab=btab)

    def _prefill_impl(self, params, state, dstate, btab, tokens, start, n,
                      reset):
        """Chunked prefill for all S slots in ONE call: slot i consumes
        ``n[i]`` prompt tokens ``tokens[i, :n[i]]`` at positions
        ``start[i]..start[i]+n[i]-1``. ``n == 0`` rows are inert: their KV
        writes land in the scratch block (all-zero table rows) and their
        state rows are frozen. One fixed (S, chunk_tokens) shape → one XLA
        program regardless of how many slots are mid-prefill."""
        from deeplearning4j_tpu.exec.programs import is_registering
        if not is_registering():
            self._m_kv_programs.inc()
        params = dequantize_tree(params)
        S = self.slots

        def wipe(a):
            r = reset.reshape((S,) + (1,) * (a.ndim - 1))
            return jnp.where(r, jnp.zeros_like(a), a)

        # a fresh slot's FIRST device call may be a prefill chunk, so the
        # reset wipe lives here too (same rule as the step)
        dstate = map_slot_leaves(wipe, dstate)
        x = jax.nn.one_hot(tokens, self.vocab, dtype=jnp.float32)
        _, new_d = self.model.prefill_chunk(params, state, dstate, x, start,
                                            n, block_tables=btab)
        live = n > 0

        def freeze(new, old):
            a = live.reshape((S,) + (1,) * (new.ndim - 1))
            return jnp.where(a, new, old)

        return map_slot_leaves(freeze, new_d, dstate)

    def _cow_impl(self, dstate, src, dst):
        """Copy-on-write: clone pool block ``src`` into ``dst`` (both (1,)
        int32) across every pool leaf. Runs when a request claims a
        partially-matching cached prefix block and will overwrite its tail."""
        from deeplearning4j_tpu.exec.programs import is_registering
        if not is_registering():
            self._m_kv_programs.inc()
        return map_pool_leaves(lambda a: a.at[dst].set(a[src]), dstate)

    # ------------------------------------------------------------ lifecycle
    def _ensure_dstate(self):
        if self._dstate is None:
            if self.kv == "paged":
                self._dstate = self.model.init_decode_state(
                    self.slots, self.max_len,
                    kv={"num_blocks": self._pool.num_blocks,
                        "block_size": self.kv_block_size})
            else:
                self._dstate = self.model.init_decode_state(self.slots,
                                                            self.max_len)
        if self._draft is not None:
            self._draft.ensure_state()

    def start(self) -> "DecodeEngine":
        self._ensure_dstate()
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(target=self._serve, daemon=True)
            self._thread.start()
        return self

    def _serve(self):
        # a program this thread builds (a request before any warm-up, a new
        # shape) is the compile ledger's ``serve``
        with compile_ledger.phase("serve"):
            self._loop()

    def stop(self) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
        err = BatcherStoppedError("decode engine stopped")
        with self._cv:
            while self._kv_ops:
                _fn, fut = self._kv_ops.popleft()
                if fut.set_running_or_notify_cancel():
                    fut.set_exception(err)
            if self._pending_restores:
                # land claimed-but-pending tier promotions so evictable
                # restored blocks hold real content across a restart
                pend, self._pending_restores = self._pending_restores, {}
                self._apply_host_rows(list(pend.items()))
            if self._pending_swap is not None:
                # a swap staged against a stopping engine still applies (and
                # unblocks its waiter) — a restart serves the new weights
                self._apply_swap_locked()
            pending = list(self._queue)
            self._queue.clear()
            live = [r for r in self._slot_reqs if r is not None]
            self._slot_reqs = [None] * self.slots
            if self._pool is not None:
                # aborted streams never publish prefix blocks (their KV is
                # incomplete); everything they claimed goes back to the pool
                for r in live:
                    for b in r.kv_blocks:
                        self._pool.decref(b)
                    r.kv_blocks = []
                for src, _dst in self._pending_cows:
                    self._pool.decref(src)   # dst was freed via r.kv_blocks
                self._pending_cows = []
                self._tables[:] = 0
                self._kv_blocked = False
        for r in pending + live:
            if not r.future.done():
                self._journal_terminal(r, "error")
                r.future.set_exception(err)

    def warmup(self, aot: Optional[str] = None):
        """Compile the (single) decode-step program through the persistent
        compile cache before the first request — runs one all-inactive step
        so a fresh process pays ~0 compile on its first ``generate``.

        ``aot``: path to an AOT artifact (exec/aot.py). Every program found
        there — the step, the paged prefill/copy-on-write side programs,
        the spec draft/verify pair — is deserialized in milliseconds
        instead of retraced; its inert warmup call below doubles as the
        validation run. ``trace_count`` stays 0 for restored programs
        (restores count in ``dl4jtpu_aot_restores_total``). Any miss falls
        back to trace-and-save, merging the fresh executable back into the
        artifact."""
        from deeplearning4j_tpu.util.compile_cache import setup_compile_cache
        setup_compile_cache()
        self._ensure_dstate()
        if self._thread is not None and self._thread.is_alive():
            return self.warmup_seconds    # loop thread owns the state now
        bundle = None
        restored = {}
        if aot is not None:
            from deeplearning4j_tpu.exec import aot as aot_mod
            p0, s0 = self._weights()
            sig = aot_mod.model_signature(p0, s0)
            bundle, _reason = aot_mod.open_bundle(aot, sig, self.precision)
            if bundle is None:
                bundle = aot_mod.AotBundle(sig, self.precision)
            originals = {k: p for k, p in self._aot_programs().items()}
            for kind in originals:
                prog = bundle.restore(self._aot_key(kind), engine=self.id)
                if prog is not None:
                    restored[kind] = prog
            self._swap_programs(restored)
        try:
            self._warmup_run()
        except Exception:
            if not restored:
                raise
            # a restored executable failed its validation run (drift the
            # artifact envelope could not catch): drop back to the traced
            # programs wholesale; the failed call may have consumed the
            # donated state trees, so rebuild them before retracing
            from deeplearning4j_tpu.exec.aot import note_miss
            note_miss("corrupt")
            self._swap_programs(originals)
            restored = {}
            self._dstate = None
            if self._draft is not None:
                self._draft._tree = None
            self._ensure_dstate()
            self._warmup_run()
        if bundle is not None and self._aot_export(bundle, restored):
            bundle.save(aot)
        return self.warmup_seconds

    def _warmup_run(self):
        with compile_ledger.phase("serve"):
            return self._warmup_programs()

    def _warmup_programs(self):
        S = self.slots
        z = np.zeros(S, np.int32)
        f = np.zeros(S, bool)
        t0 = time.perf_counter()
        params, state = self._weights()
        c0 = self._m_compiled.value
        step_args = (z, z, f, f, np.zeros(S, np.uint32),
                     np.zeros(S, np.float32), z)
        if self.kv == "paged":
            step_args = (np.zeros((S, self.kv_max_blocks), np.int32),
                         ) + step_args
        tok, self._dstate = self._step(params, state, self._dstate,
                                       *step_args)
        jax.block_until_ready(tok)
        # the paged side programs compile here too — a no-op chunk (every
        # n == 0) and a scratch self-copy leave the state bitwise intact
        if self._prefill is not None:
            self._dstate = self._prefill(
                params, state, self._dstate,
                np.zeros((S, self.kv_max_blocks), np.int32),
                np.zeros((S, self.chunk_tokens), np.int32), z, z, f)
        if self._cow is not None:
            self._dstate = self._cow(self._dstate, np.zeros(1, np.int32),
                                     np.zeros(1, np.int32))
        if self._spec is not None:
            # the draft and verify programs compile here too: an
            # all-inert draft tick and an all-inert verify (n_in == 0
            # everywhere) leave both state trees bitwise intact
            zk = np.zeros((S, self._spec_k), np.int32)
            zn = np.zeros((S, self._spec_tree.n_nodes), np.int32)
            u, fl = np.zeros(S, np.uint32), np.zeros(S, np.float32)
            self._draft.step(zk, z, z, z, z, f, u, fl, z)
            vargs = (zn, z, z, f, u, fl, z)
            if self.kv == "paged":
                vargs = (np.zeros((S, self.kv_max_blocks), np.int32),
                         ) + vargs
            *_, self._dstate = self._verifier.run(
                params, state, self._dstate, *vargs)
        jax.block_until_ready(self._dstate)
        self.warmup_seconds = time.perf_counter() - t0
        if self._m_compiled.value > c0:
            self._register_program(params, state, step_args,
                                   self.warmup_seconds)
        return self.warmup_seconds

    # ---------------------------------------------------------------- AOT
    def _aot_programs(self) -> dict:
        """The engine's hot programs by artifact kind (the current
        callables — traced jits before a restore, Compiled after)."""
        progs = {"step": self._step}
        if self._prefill is not None:
            progs["prefill"] = self._prefill
        if self._cow is not None:
            progs["cow"] = self._cow
        if self._draft is not None:
            progs["draft"] = self._draft._run
            progs["verify"] = self._verifier._jit
        return progs

    def _swap_programs(self, progs: dict) -> None:
        if "step" in progs:
            self._step = progs["step"]
        if "prefill" in progs:
            self._prefill = progs["prefill"]
        if "cow" in progs:
            self._cow = progs["cow"]
        if self._draft is not None:
            if "draft" in progs:
                self._draft._run = progs["draft"]
            if "verify" in progs:
                self._verifier._jit = progs["verify"]

    def _aot_key(self, kind: str) -> str:
        """Artifact key of one decode program: every shape-determining
        knob is in the key, so a config change is a key miss (retrace),
        never a stale restore."""
        parts = [f"decode:{kind}", f"S{self.slots}", f"L{self.max_len}",
                 f"kv={self.kv}"]
        if self.kv == "paged":
            parts.append(f"bs{self.kv_block_size}"
                         f":nb{self._pool.num_blocks}")
        if kind == "prefill":
            parts.append(f"c{self.chunk_tokens}")
        if kind in ("draft", "verify"):
            # the tree shape sizes both programs (draft scan width is
            # d+1, verify window is the node count)
            parts.append(
                "t" + ",".join(str(k) for k in self._spec_tree.kvec))
        if kind == "draft":
            from deeplearning4j_tpu.exec import aot as aot_mod
            dp, ds = self._draft._weights()
            parts.append(aot_mod.model_signature(dp, ds)[:12])
        return ":".join(parts)

    def _aot_export(self, bundle, restored: dict) -> int:
        """Serialize every program NOT restored into ``bundle`` (the
        trace-and-save half); returns how many were added."""
        from deeplearning4j_tpu.exec import aot as aot_mod
        S = self.slots
        params, state = self._weights()
        z = np.zeros(S, np.int32)
        f = np.zeros(S, bool)
        u, fl = np.zeros(S, np.uint32), np.zeros(S, np.float32)
        added = 0

        def put(kind, fn, args):
            nonlocal added
            if kind in restored:
                return                  # already in the artifact
            bundle.add_compiled(self._aot_key(kind),
                                aot_mod.export_compiled(fn, args))
            added += 1

        step_args = (z, z, f, f, u, fl, z)
        if self.kv == "paged":
            step_args = (np.zeros((S, self.kv_max_blocks), np.int32),
                         ) + step_args
        put("step", self._step, (params, state, self._dstate) + step_args)
        if self._prefill is not None:
            put("prefill", self._prefill,
                (params, state, self._dstate,
                 np.zeros((S, self.kv_max_blocks), np.int32),
                 np.zeros((S, self.chunk_tokens), np.int32), z, z, f))
        if self._cow is not None:
            put("cow", self._cow,
                (self._dstate, np.zeros(1, np.int32), np.zeros(1, np.int32)))
        if self._draft is not None:
            zk = np.zeros((S, self._spec_k), np.int32)
            zn = np.zeros((S, self._spec_tree.n_nodes), np.int32)
            dp, ds = self._draft._weights()
            put("draft", self._draft._run,
                (dp, ds, self._draft._tree, zk, z, z, z, z, f, u, fl, z))
            vargs = (zn, z, z, f, u, fl, z)
            if self.kv == "paged":
                vargs = (np.zeros((S, self.kv_max_blocks), np.int32),
                         ) + vargs
            put("verify", self._verifier._jit,
                (params, state, self._dstate) + vargs)
        return added

    def _register_program(self, params, state, step_args, wall):
        """Record the (single) decode-step program's cost/memory analysis
        in the process program registry (``GET /programs``, MFU gauges).
        Uses the post-step ``self._dstate`` — same shapes as the donated
        input state."""
        from deeplearning4j_tpu.exec.programs import get_programs
        get_programs().record(self.id, "step", self._step,
                              (params, state, self._dstate) + tuple(step_args),
                              compile_seconds=wall)

    # ------------------------------------------------------------ scheduler
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               seed: int = 0, temperature: float = 0.0,
               top_k: int = 0, request_id: Optional[str] = None,
               tenant: str = "default", priority: str = "normal") -> Future:
        """Enqueue one generation request; returns a Future resolving to
        ``{"tokens": [...], "prompt_len": int}``. ``request_id`` /
        ``tenant`` / ``priority`` ride into the request's wide-event
        journal record (and histogram exemplars)."""
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("prompt must contain at least one token id")
        if not all(0 <= t < self.vocab for t in prompt):
            raise ValueError(f"token ids must be in [0, {self.vocab})")
        if len(prompt) + int(max_new_tokens) > self.max_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens})"
                f" exceeds engine capacity max_len={self.max_len}")
        if self._pool is not None:
            need = blocks_for_span(len(prompt) + int(max_new_tokens) - 1,
                                   self.kv_block_size)
            if need > self._pool.usable:
                raise ValueError(
                    f"request needs {need} KV blocks "
                    f"(block_size={self.kv_block_size}) but the pool holds "
                    f"{self._pool.usable} — it could never be admitted")
        if self._stop.is_set() and self._thread is not None:
            raise BatcherStoppedError("decode engine stopped")
        fut = Future()
        ctx = get_context()
        req = _Request(prompt, max_new_tokens, seed, temperature, top_k, fut,
                       rid=request_id, tenant=tenant, priority=priority,
                       trace_id=ctx.trace_id if ctx is not None else None)
        with self._cv:
            if len(self._queue) >= self.max_queue:
                # a rejected request still leaves exactly one terminal
                # wide event — the journal never under-counts sheds
                self._journal_terminal(req, "shed")
                raise ServerOverloadedError(
                    f"decode queue full ({self.max_queue})")
            self._queue.append(req)
            self._cv.notify_all()
        return fut

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 32,
                 seed: int = 0, temperature: float = 0.0,
                 top_k: int = 0, timeout: Optional[float] = None,
                 request_id: Optional[str] = None, tenant: str = "default",
                 priority: str = "normal") -> dict:
        """Blocking ``submit`` — the one-call API the HTTP endpoint uses."""
        return self.submit(prompt, max_new_tokens, seed, temperature,
                           top_k, request_id=request_id, tenant=tenant,
                           priority=priority).result(timeout=timeout)

    def _admit_locked(self):
        if self._pending_swap is not None:
            return          # admission pauses so live slots can drain
        blocked = False
        for i in range(self.slots):
            if not self._queue:
                break
            if self._slot_reqs[i] is not None:
                continue
            r = self._queue[0]
            if self._pool is not None:
                try:
                    self._claim_kv(r, i)
                except PoolExhaustedError:
                    # head-of-line blocking is deliberate: the request at
                    # the queue head admits as soon as blocks free up (no
                    # starvation of long prompts by short ones)
                    if not self._kv_blocked:
                        self._m_kv_exhausted.inc()
                    blocked = True
                    break
            self._queue.popleft()
            self._slot_reqs[i] = r
            r.t_admit = time.perf_counter()
            self._m_queue.observe(r.t_admit - r.t_start, exemplar=r.rid)
        if self._pool is not None:
            self._kv_blocked = blocked

    def _claim_kv(self, r, slot):
        """Claim pool blocks + build the page-table row for one admitted
        request (loop thread, under ``self._cv``). Prefix-cache hits claim
        cached blocks read-only (refcount++) and skip their prefill span;
        a partial tail match is claimed via copy-on-write. All-or-nothing:
        on exhaustion every claimed ref is returned and the request stays
        queued."""
        bs = self.kv_block_size
        plen = len(r.prompt)
        # KV positions written: 0 .. plen + max_new - 2 (the final sampled
        # token is returned, never fed back)
        need = blocks_for_span(plen + r.max_new - 1, bs)
        shared, cow, skip = [], None, 0
        if self._prefix is not None:
            r0 = (self._m_host_restores.value
                  if self._host_tier is not None else 0)
            shared, cow, skip = self._prefix.match(r.prompt)
            if self._host_tier is not None:
                # match runs serially on the loop thread, so the counter
                # delta is exactly this request's tier promotions
                r.host_restores = int(self._m_host_restores.value - r0)
        try:
            fresh = self._pool.alloc(need - len(shared))
        except PoolExhaustedError:
            for b in shared:
                self._pool.decref(b)
            if cow is not None:
                self._pool.decref(cow[0])
            raise
        if cow is not None:
            # clone the partially-matching cached block into our first
            # fresh block; the copy program runs before this slot's first
            # prefill/step, and the source ref is dropped after the copy
            self._pending_cows.append((cow[0], fresh[0]))
        if skip:
            self._m_prefix_hits.inc()
            self._m_prefix_saved.inc(skip)
        r.kv_blocks = shared + fresh
        r.cursor = skip                  # prefill resumes past the reuse
        r.prefix_hit = skip
        row = self._tables[slot]
        row[:] = 0
        row[:need] = r.kv_blocks

    def _release_kv(self, slot, r):
        """Return a finished request's blocks to the pool (loop thread).
        Publication into the prefix cache happens FIRST so blocks whose
        refcount drops to zero park in the evictable LRU instead of the
        free list. This is the full-release path slot re-claim depends on:
        occupancy returns to baseline once nothing references the blocks."""
        if not r.kv_blocks:
            return
        if self._prefix is not None:
            self._prefix.insert(r.prompt, r.kv_blocks)
        for b in r.kv_blocks:
            self._pool.decref(b)
        r.kv_blocks = []
        self._tables[slot][:] = 0

    # ------------------------------------------------------ wide events
    def _journal_terminal(self, r, outcome, kv_peak: int = 0):
        """Append the request's ONE terminal wide event (completions and
        rejections alike). Pure host-side bookkeeping — no device work."""
        now = time.perf_counter()
        phases = {}
        if r.t_admit is not None:
            phases["queue"] = r.t_admit - r.t_start
            if r.t_first is not None:
                phases["prefill"] = r.t_first - r.t_admit
                phases["decode"] = (r.t_last or r.t_first) - r.t_first
        else:
            phases["queue"] = now - r.t_start
        if r.verify_s:
            phases["verify"] = r.verify_s
        rec = new_record(
            r.rid, "decode",
            trace_id=r.trace_id, outcome=outcome,
            tenant=r.tenant, priority=r.priority,
            engine=self.id, model_version=self._version,
            tokens_in=len(r.prompt), tokens_out=len(r.generated),
            wall_seconds=(r.t_last or now) - r.t_start,
            ttft_seconds=(r.t_first - r.t_start
                          if r.t_first is not None else None),
            first_prefill_chunk_seconds=(r.t_prefill0 - r.t_start
                                         if r.t_prefill0 is not None
                                         else None),
            phases=phases)
        if self._spec is not None:
            rec["spec"] = {"drafted": r.drafted, "accepted": r.accepted}
        if self._pool is not None:
            rec["kv"] = {"peak_blocks": kv_peak,
                         "prefix_hit_depth": r.prefix_hit,
                         "host_restores": r.host_restores}
        self.journal.append(rec)

    def _finish(self, slot, r, outcome):
        """Terminal accounting for one completed stream (loop thread):
        KV peak is captured BEFORE the release clears the block list,
        the slot is freed, the wide event lands, the future resolves."""
        kv_peak = len(r.kv_blocks)
        if self._pool is not None:
            self._release_kv(slot, r)
        with self._cv:
            self._slot_reqs[slot] = None   # freed; wiped on re-claim
        self._m_requests.inc()
        self._journal_terminal(r, outcome, kv_peak=kv_peak)
        r.future.set_result({"tokens": r.generated,
                             "prompt_len": len(r.prompt)})

    # ----------------------------------------- host-side block movement
    # Migration, spill, and restore move KV as HOST bytes: one numpy
    # gather/scatter per pool leaf with a jnp.asarray round-trip back into
    # the (re-donated) decode-state tree. No jitted gather/scatter program
    # exists for any of it — the compile-count pins (one step program, ≤2
    # kv side programs) are untouched by design.

    def _pool_leaf_items(self):
        """``[(key, leaf)]`` for the pool leaves of the decode state,
        with tree-path keys stable across engines of the same model (the
        migration wire format's leaf identity)."""
        from deeplearning4j_tpu.serving.kv import is_pool_path
        flat, _ = jax.tree_util.tree_flatten_with_path(self._dstate)
        return [(jax.tree_util.keystr(path), leaf)
                for path, leaf in flat if is_pool_path(path)]

    def _gather_rows(self, bids):
        """Per-leaf host gather of the given blocks: key -> ``(n, bs, H,
        Dh)`` numpy array."""
        idx = np.asarray(bids, np.int64)
        return {k: np.asarray(leaf)[idx]
                for k, leaf in self._pool_leaf_items()}

    def _apply_host_rows(self, writes):
        """Scatter ``[(bid, {leaf key: (bs, H, Dh) row})]`` into the pool
        leaves through one host round-trip per touched leaf."""
        if not writes:
            return
        from deeplearning4j_tpu.serving.kv import is_pool_path
        flat, treedef = jax.tree_util.tree_flatten_with_path(self._dstate)
        leaves = [leaf for _, leaf in flat]
        keymap = {jax.tree_util.keystr(path): i
                  for i, (path, _) in enumerate(flat)
                  if is_pool_path(path)}
        arrs = {}
        for bid, rows in writes:
            for key, row in rows.items():
                i = keymap[key]
                if i not in arrs:
                    arrs[i] = np.array(leaves[i])
                arrs[i][bid] = row
        for i, a in arrs.items():
            leaves[i] = jnp.asarray(a)
        self._dstate = jax.tree_util.tree_unflatten(treedef, leaves)

    # ------------------------------------------------- host-tier spill/restore
    def _spill_block(self, chain_hash, parent, tokens, bid):
        """Pool-eviction hook (loop thread, via PrefixCache._drop):
        demote the evicted block's device rows to the host tier. Must
        never raise — an exception here would leak the block mid-alloc —
        so any failure degrades to a plain drop."""
        try:
            if self._pending_restores.pop(bid, None) is not None:
                # the block was claimed from the tier but its data never
                # landed on device; the tier still holds the content
                return
            rows = self._gather_rows([bid])
            self._host_tier.put(chain_hash, parent, tokens,
                                {k: v[0] for k, v in rows.items()})
        except Exception:
            pass

    def _restore_block(self, chain_hash, tokens):
        """Second-chance hook (loop thread, from PrefixCache.match):
        claim a fresh pool block for a tier hit and queue its host→device
        scatter on the pre-step batch. Returns the bid (refcount 1 — the
        claim belongs to the matching request) or None under pool
        pressure, which the cache treats as a plain miss."""
        entry = self._host_tier.get(chain_hash)
        if entry is None:
            return None
        try:
            bid = self._pool.alloc(1)[0]
        except PoolExhaustedError:
            return None
        self._pending_restores[bid] = entry.rows
        self._m_host_restores.inc()
        return bid

    # ------------------------------------------------------------ migration
    def _drain_kv_ops_locked(self):
        """Run queued export/import closures (caller holds ``self._cv``,
        loop thread, step boundary — the only point where the donated
        decode state may be read or rebuilt)."""
        while self._kv_ops:
            fn, fut = self._kv_ops.popleft()
            if fut.set_running_or_notify_cancel():
                try:
                    fut.set_result(fn())
                except BaseException as e:
                    fut.set_exception(e)

    def _run_kv_op(self, fn):
        """Marshal ``fn`` onto the loop thread (or run it inline at a
        safe point when the loop isn't running) and return its result."""
        fut = Future()
        with self._cv:
            if self._thread is not None and self._thread.is_alive():
                self._kv_ops.append((fn, fut))
                self._cv.notify_all()
            else:
                self._ensure_dstate()
                if fut.set_running_or_notify_cancel():
                    try:
                        fut.set_result(fn())
                    except BaseException as e:
                        fut.set_exception(e)
        return fut.result(timeout=60.0)

    def _migrate_envelope(self):
        """The validity envelope a payload must match to land here: the
        AOT-bundle discipline (exec/aot.py) applied to KV — same
        architecture (shape/dtype signature of the SERVING weights), same
        serving precision, same block geometry, same vocabulary."""
        from deeplearning4j_tpu.exec import aot as aot_mod
        p, s = self._weights()
        return {"model_sig": aot_mod.model_signature(p, s),
                "precision": self.precision,
                "block_size": self.kv_block_size,
                "vocab": int(self.vocab)}

    def kv_export(self, prompt: Sequence[int]) -> dict:
        """Serialize the cached block chain covering ``prompt``'s full
        blocks into a migration payload (kv/migrate.py) — the
        prefill-replica half of disaggregated serving. The chain must
        already be published (the prefill ran to completion here);
        otherwise ``KVMigrateError(reason='no_chain')``."""
        from deeplearning4j_tpu.serving.kv import KVMigrateError, pack_chain
        from deeplearning4j_tpu.serving.kv.prefix import _ROOT, _chain_hash
        if self._prefix is None:
            raise ValueError(
                "kv_export requires kv='paged' with prefix_cache=True")
        toks = [int(t) for t in prompt]

        def op():
            bs = self.kv_block_size
            bids, chain = [], []
            h = _ROOT
            for j in range(len(toks) // bs):
                blk = toks[j * bs:(j + 1) * bs]
                h = _chain_hash(h, blk)
                bid = self._prefix._by_hash.get(h)
                if bid is None:
                    break
                bids.append(bid)
                chain.extend(blk)
            if not bids:
                raise KVMigrateError(
                    "no cached chain covers this prompt's first block — "
                    "run the prefill to completion here before exporting",
                    reason="no_chain")
            payload = pack_chain(self._gather_rows(bids), chain,
                                 self._migrate_envelope())
            self._m_migrate_exports.inc()
            return payload

        return self._run_kv_op(op)

    def kv_import(self, payload: dict) -> dict:
        """Restore a migrated chain into this engine's pool: validate the
        whole payload against the local envelope (no side effects on any
        mismatch), allocate fresh blocks, scatter the rows host-side, and
        rebind the page-table identity by re-indexing the same token
        chain in the prefix cache — continued decode is then an ordinary
        (bitwise-exact) prefix hit. The decode-replica half."""
        from deeplearning4j_tpu.serving.kv import (KVMigrateError,
                                                   unpack_chain)
        if self._prefix is None:
            raise ValueError(
                "kv_import requires kv='paged' with prefix_cache=True")

        def op():
            leaves = dict(self._pool_leaf_items())
            tokens, rows = unpack_chain(payload, self._migrate_envelope(),
                                        leaves)
            n = len(tokens) // self.kv_block_size
            try:
                bids = self._pool.alloc(n)
            except PoolExhaustedError as e:
                raise KVMigrateError(
                    f"destination pool cannot hold the chain: {e}",
                    reason="exhausted")
            self._apply_host_rows(
                [(bid, {k: rows[k][j] for k in rows})
                 for j, bid in enumerate(bids)])
            added = self._prefix.insert(tokens, bids)
            for b in bids:
                # indexed blocks park in the evictable LRU (cache
                # entries); blocks the chain already had free right back
                self._pool.decref(b)
            self._m_migrate_imports.inc()
            return {"imported_blocks": added,
                    "duplicate_blocks": n - added, "tokens": len(tokens)}

        try:
            return self._run_kv_op(op)
        except KVMigrateError as e:
            self._m_migrate_rejects.labels(
                engine=self.id, reason=e.reason).inc()
            raise

    def _loop(self):
        S = self.slots
        while not self._stop.is_set():
            with self._cv:
                self._drain_kv_ops_locked()
                if (self._pending_swap is not None
                        and all(r is None for r in self._slot_reqs)):
                    # step boundary with no live slots: every in-flight
                    # generation ran end-to-end on the old weights
                    self._apply_swap_locked()
                self._admit_locked()
                live = [(i, r) for i, r in enumerate(self._slot_reqs)
                        if r is not None]
                if not live:
                    self._cv.wait(timeout=0.05)
                    continue
            params, state = self._weights()
            if self._pending_restores:
                # host-tier promotions land BEFORE anything can read the
                # claimed blocks — including the CoW program below, whose
                # source may itself be a just-restored block
                with self._cv:
                    pend, self._pending_restores = self._pending_restores, {}
                self._apply_host_rows(list(pend.items()))
            if self._pending_cows:
                # copy-on-write claims run BEFORE the claimer's first
                # prefill/step can read (or overwrite) the cloned block
                cows, self._pending_cows = self._pending_cows, []
                for src, dst in cows:
                    self._dstate = self._cow(self._dstate,
                                             np.full(1, src, np.int32),
                                             np.full(1, dst, np.int32))
                    self._pool.decref(src)
                    self._m_cow.inc()
            if self.chunk_tokens is not None:
                # chunked prefill rides the same iteration cadence: slots
                # still consuming their prompt advance by up to K positions
                # per iteration while decode-phase slots step one token
                pre = [(i, r) for i, r in live
                       if r.cursor < len(r.prompt) - 1]
                if pre:
                    K = self.chunk_tokens
                    ptok = np.zeros((S, K), np.int32)
                    pstart = np.zeros(S, np.int32)
                    pn = np.zeros(S, np.int32)
                    preset = np.zeros(S, bool)
                    t_chunk = time.perf_counter()
                    for i, r in pre:
                        k = min(K, len(r.prompt) - 1 - r.cursor)
                        ptok[i, :k] = r.prompt[r.cursor:r.cursor + k]
                        pstart[i] = r.cursor
                        pn[i] = k
                        preset[i] = r.fresh
                        r.fresh = False
                        r.cursor += k
                        if r.t_prefill0 is None:
                            r.t_prefill0 = t_chunk
                    with trace.span("decode_prefill", chunks=len(pre)):
                        self._dstate = self._prefill(
                            params, state, self._dstate,
                            jnp.asarray(self._tables), ptok, pstart, pn,
                            preset)
                    self._m_prefill_chunks.inc(len(pre))
                    self._m_prefill_tokens.inc(int(pn.sum()))
                # slots that finished their chunk this iteration join the
                # step below (cursor is now at the last prompt position)
                live = [(i, r) for i, r in live
                        if r.cursor >= len(r.prompt) - 1]
                if not live:
                    continue
            if self._spec is not None:
                self._tick_spec(live, params, state)
                continue
            tokens = np.zeros(S, np.int32)
            pos = np.zeros(S, np.int32)
            reset = np.zeros(S, bool)
            active = np.zeros(S, bool)
            seeds = np.zeros(S, np.uint32)
            temps = np.zeros(S, np.float32)
            topk = np.zeros(S, np.int32)
            for i, r in live:
                active[i] = True
                reset[i] = r.fresh
                r.fresh = False
                p = r.cursor
                tokens[i] = (r.prompt[p] if p < len(r.prompt)
                             else r.generated[-1])
                pos[i] = p
                seeds[i] = r.seed & 0xFFFFFFFF
                temps[i] = r.temperature
                topk[i] = r.top_k
            t0 = time.perf_counter()
            c0 = self._m_compiled.value
            step_args = (tokens, pos, reset, active, seeds, temps, topk)
            if self._pool is not None:
                # inactive slots get an all-zero table row so their masked
                # write lands in the scratch block — a mid-prefill slot's
                # REAL row here would let the step corrupt its block 0
                btab = np.where(active[:, None], self._tables, 0)
                step_args = (jnp.asarray(btab.astype(np.int32)),) + step_args
            with trace.span("decode_step", active=len(live)):
                nt, self._dstate = self._step(params, state, self._dstate,
                                              *step_args)
                nt = np.asarray(nt)
            dt = time.perf_counter() - t0
            if self._m_compiled.value > c0:
                self._register_program(params, state, step_args, dt)
            self._decode_seconds += dt
            self._m_steps.inc()
            self._m_occupancy.set(len(live))
            self._m_token_seconds.observe(dt)
            now = t0 + dt                        # the post-sync host stamp
            done = []
            for i, r in live:
                r.cursor += 1
                if r.cursor < len(r.prompt):
                    if r.t_prefill0 is None:
                        r.t_prefill0 = now
                    continue                     # still prefilling
                tok = int(nt[i])
                r.generated.append(tok)
                self._m_tokens.inc()
                if r.t_first is None:
                    if r.t_prefill0 is None:
                        r.t_prefill0 = now       # 1-token prompt: the
                    r.t_first = now              # prefill WAS this step
                    self._m_ttft.observe(now - r.t_start, exemplar=r.rid)
                else:
                    self._m_itl.observe(now - r.t_last, exemplar=r.rid)
                r.t_last = now
                if ((self.eos_id is not None and tok == self.eos_id)
                        or len(r.generated) >= r.max_new
                        or r.cursor >= self.max_len):
                    outcome = ("eos" if (self.eos_id is not None
                                         and tok == self.eos_id)
                               else "max_new")
                    done.append((i, r, outcome))
            for i, r, outcome in done:
                # full release on eos/length: every claimed block's
                # refcount returns to the pool (prefix-cached blocks
                # park in the evictable LRU, everything else frees)
                self._finish(i, r, outcome)
        self._m_occupancy.set(0)

    # ------------------------------------------------------- speculative tick
    def _tick_spec(self, live, params, state):
        """One speculative scheduler iteration. At most THREE device calls
        regardless of slot mix, each a fixed-shape compiled-once program:

        1. one DRAFT call — prompt catch-up rows (the draft prefills the
           prompt independently, up to k positions per tick) and ready
           generation rows (propose k tokens) share it, masks not shapes;
        2. one target STEP — rows still consuming their prompt through
           the plain path (no chunked prefill) ride the ordinary step
           program with its sampled output ignored;
        3. one VERIFY — every ready row's k-token window in one batched
           multi-position target step; the host appends the oracle's
           emitted prefix (1..k tokens per slot per tick).

        A row is 'ready' once the draft has caught up to the target
        cursor; a fresh slot becomes ready after ceil((plen-1)/k) draft
        ticks, which overlap the target's own prefill steps. Catch-up
        feeds the whole known STREAM (prompt + generated), not just the
        prompt: a side-branch acceptance leaves the draft's carries
        behind the emitted stream (its snapshots follow its own spine),
        and the resync path replays the emitted tokens it missed."""
        S, K = self.slots, self._spec_k
        tr = self._spec_tree

        def stok(r, p):
            pl = len(r.prompt)
            return r.prompt[p] if p < pl else r.generated[p - pl]

        catchup, ready, tpre = [], [], []
        for i, r in live:
            plen = len(r.prompt)
            known = plen + len(r.generated)
            if r.cursor < plen - 1:
                tpre.append((i, r))
            if r.draft_cursor < known - 1:
                catchup.append((i, r, known))
            elif r.cursor >= plen - 1 and r.draft_cursor == r.cursor:
                # the window may not outrun the request budget or the KV
                # capacity — same write bound as the plain path
                n_in = min(K, r.max_new - len(r.generated),
                           self.max_len - r.cursor)
                if n_in > 0:
                    ready.append((i, r, n_in))
        dprops = dsides = None
        if catchup or ready:
            given = np.zeros((S, K), np.int32)
            n_given = np.zeros(S, np.int32)
            n_steps = np.zeros(S, np.int32)
            dpos = np.zeros(S, np.int32)
            sel = np.zeros(S, np.int32)
            dreset = np.zeros(S, bool)
            dseeds = np.zeros(S, np.uint32)
            dtemps = np.zeros(S, np.float32)
            dtopk = np.zeros(S, np.int32)
            for i, r, known in catchup:
                m = min(K, known - 1 - r.draft_cursor)
                given[i, :m] = [stok(r, p) for p in
                                range(r.draft_cursor, r.draft_cursor + m)]
                n_given[i] = m
                n_steps[i] = m
                dpos[i] = r.draft_cursor
                sel[i] = r.draft_sel
                dreset[i] = r.draft_fresh
                r.draft_fresh = False
                r.draft_cursor += m
                r.draft_sel = m - 1
            for i, r, n_in in ready:
                p = r.cursor
                given[i, 0] = stok(r, p)
                n_given[i] = 1
                n_steps[i] = n_in
                dpos[i] = p
                sel[i] = r.draft_sel
                dreset[i] = r.draft_fresh
                r.draft_fresh = False
                dseeds[i] = r.seed & 0xFFFFFFFF
                dtemps[i] = r.temperature
                dtopk[i] = r.top_k
            t0 = time.perf_counter()
            with trace.span("spec_draft", rows=len(catchup) + len(ready)):
                dprops, dsides = self._draft.step(given, n_given, n_steps,
                                                  dpos, sel, dreset,
                                                  dseeds, dtemps, dtopk)
            self._m_spec_draft_seconds.observe(time.perf_counter() - t0)
        if tpre:
            # plain-path prompt consumption rides the ordinary step
            # program (the sampled token is ignored mid-prompt, exactly
            # as in the non-speculative loop)
            tokens = np.zeros(S, np.int32)
            pos = np.zeros(S, np.int32)
            reset = np.zeros(S, bool)
            active = np.zeros(S, bool)
            seeds = np.zeros(S, np.uint32)
            temps = np.zeros(S, np.float32)
            topk = np.zeros(S, np.int32)
            for i, r in tpre:
                active[i] = True
                reset[i] = r.fresh
                r.fresh = False
                tokens[i] = r.prompt[r.cursor]
                pos[i] = r.cursor
                seeds[i] = r.seed & 0xFFFFFFFF
                temps[i] = r.temperature
                topk[i] = r.top_k
            t0 = time.perf_counter()
            c0 = self._m_compiled.value
            step_args = (tokens, pos, reset, active, seeds, temps, topk)
            if self._pool is not None:
                btab = np.where(active[:, None], self._tables, 0)
                step_args = (jnp.asarray(btab.astype(np.int32)),) + step_args
            with trace.span("decode_step", active=len(tpre)):
                _, self._dstate = self._step(params, state, self._dstate,
                                             *step_args)
            dt = time.perf_counter() - t0
            if self._m_compiled.value > c0:
                self._register_program(params, state, step_args, dt)
            self._decode_seconds += dt
            self._m_steps.inc()
            for i, r in tpre:
                r.cursor += 1
                if r.t_prefill0 is None:
                    r.t_prefill0 = t0 + dt
        done = []
        if ready:
            vtok = np.zeros((S, tr.n_nodes), np.int32)
            vpos = np.zeros(S, np.int32)
            vn = np.zeros(S, np.int32)
            vreset = np.zeros(S, bool)
            vseeds = np.zeros(S, np.uint32)
            vtemps = np.zeros(S, np.float32)
            vtopk = np.zeros(S, np.int32)
            for i, r, n_in in ready:
                # the slot's token tree: node 0 = the last emitted (or
                # final prompt) token; each depth-d group = the draft's
                # own proposal (the spine continuation, child 0) plus
                # its k_d-1 masked top-logit alternatives — every node
                # is judged against the oracle computed from the
                # target's distribution AT that node
                vtok[i, 0] = given[i, 0]
                for dd in range(1, tr.d + 1):
                    fst, kd = int(tr.first[dd - 1]), tr.kvec[dd - 1]
                    vtok[i, fst] = dprops[i, dd - 1]
                    if kd > 1:
                        vtok[i, fst + 1:fst + kd] = dsides[i, dd - 1,
                                                           :kd - 1]
                vpos[i] = r.cursor
                vn[i] = n_in
                vreset[i] = r.fresh
                r.fresh = False
                vseeds[i] = r.seed & 0xFFFFFFFF
                vtemps[i] = r.temperature
                vtopk[i] = r.top_k
            vargs = (vtok, vpos, vn, vreset, vseeds, vtemps, vtopk)
            if self._pool is not None:
                vlive = vn > 0
                btab = np.where(vlive[:, None], self._tables, 0)
                vargs = (jnp.asarray(btab.astype(np.int32)),) + vargs
            t0 = time.perf_counter()
            with trace.span("spec_verify", rows=len(ready)):
                etoks, acc, emit, sacc, self._dstate = self._verifier.run(
                    params, state, self._dstate, *vargs)
            dt = time.perf_counter() - t0
            now = t0 + dt                       # one stamp per verify run
            self._decode_seconds += dt
            self._m_steps.inc()
            self._m_token_seconds.observe(dt)
            drafted = accepted = 0
            for i, r, n_in in ready:
                # judged proposals: tree depths 1..min(d, n_in-1) plus
                # the budget-capped bonus slot — min(d, n_in) keeps the
                # rate's ceiling at 1.0 for full spine acceptance
                drafted += min(tr.d, n_in)
                accepted += int(acc[i])
                r.drafted += min(tr.d, n_in)
                r.accepted += int(acc[i])
                r.verify_s += dt
                self._m_spec_depth.observe(float(acc[i]))
                p0 = r.cursor
                consumed, finished, fin_eos = 0, False, False
                for j in range(int(emit[i])):
                    tok = int(etoks[i, j])
                    r.generated.append(tok)
                    self._m_tokens.inc()
                    consumed += 1
                    if ((self.eos_id is not None and tok == self.eos_id)
                            or len(r.generated) >= r.max_new
                            or r.cursor + consumed >= self.max_len):
                        finished = True
                        fin_eos = (self.eos_id is not None
                                   and tok == self.eos_id)
                        break
                if consumed:
                    # a verify emits an accepted RUN at one host point:
                    # one ITL sample per accepted token (run wall spread
                    # over the run), TTFT on the stream's first token
                    per = (now - (r.t_last if r.t_last is not None
                                  else r.t_start)) / consumed
                    if r.t_first is None:
                        r.t_first = now
                        self._m_ttft.observe(now - r.t_start,
                                             exemplar=r.rid)
                        n_itl = consumed - 1
                    else:
                        n_itl = consumed
                    for _ in range(n_itl):
                        self._m_itl.observe(per, exemplar=r.rid)
                    r.t_last = now
                r.cursor += consumed
                # draft resync: its carry snapshots follow its OWN spine,
                # valid through the spine-consistent accepted prefix —
                # resume from snapshot js (never past the emitted stream);
                # a side-branch acceptance leaves draft_cursor short and
                # the catch-up path replays the gap next tick
                js = max(0, min(consumed - 1, int(sacc[i])))
                r.draft_cursor = p0 + js + 1
                r.draft_sel = js
                if finished:
                    done.append((i, r, "eos" if fin_eos else "max_new"))
            self._m_spec_drafted.inc(drafted)
            self._m_spec_accepted.inc(accepted)
            tot = self._m_spec_drafted.value
            self._m_spec_rate.set(
                self._m_spec_accepted.value / tot if tot else 0.0)
        self._m_occupancy.set(len(live))
        for i, r, outcome in done:
            self._finish(i, r, outcome)

    # --------------------------------------------------------------- stats
    def _slo_stats(self) -> dict:
        """Request-lifecycle SLO snapshot: percentiles + the per-bucket
        last-exemplar request ids that link a bucket back to its journal
        record (docs/OBSERVABILITY.md "Request lifecycle")."""
        def block(h):
            out = {"count": int(h.count)}
            for q, key in ((0.5, "p50_ms"), (0.99, "p99_ms")):
                p = h.percentile(q)
                out[key] = round(p * 1e3, 4) if p is not None else None
            out["exemplars"] = [
                ["+Inf" if b == float("inf") else b, rid, v]
                for b, rid, v in h.exemplars()]
            return out
        return {"ttft": block(self._m_ttft),
                "itl": block(self._m_itl),
                "queue": block(self._m_queue)}

    def stats(self) -> dict:
        with self._cv:
            occupied = sum(r is not None for r in self._slot_reqs)
            queued = len(self._queue)
        toks = self._m_tokens.value
        kv = None
        if self._pool is not None:
            kv = dict(self.kv_pool_info())
            kv.update({
                "prefix_cache": self._prefix is not None,
                "chunk_tokens": self.chunk_tokens,
                "kv_programs": int(self._m_kv_programs.value),
                "prefix_hits": int(self._m_prefix_hits.value),
                "prefix_tokens_saved": int(self._m_prefix_saved.value),
                "cow_copies": int(self._m_cow.value),
                "prefill_chunks": int(self._m_prefill_chunks.value),
                "prefill_tokens": int(self._m_prefill_tokens.value),
                "exhausted_events": int(self._m_kv_exhausted.value),
                "migrate_exports": int(self._m_migrate_exports.value),
                "migrate_imports": int(self._m_migrate_imports.value),
            })
            if self._prefix is not None:
                # bounded chain-head digest: the prefix-affinity routing
                # signal the router scrapes from /stats
                kv["chain_heads"] = self._prefix.chain_heads()
            if self._host_tier is not None:
                kv["host_restores"] = int(self._m_host_restores.value)
        spec = None
        if self._spec is not None:
            drafted = int(self._m_spec_drafted.value)
            accepted = int(self._m_spec_accepted.value)
            depth = self._m_spec_depth
            spec = {"k": self._spec_tree.d,
                    "tree": list(self._spec_tree.kvec),
                    "tree_nodes": self._spec_tree.n_nodes,
                    "self_draft": self._spec.self_draft,
                    "draft_precision": self._draft.precision,
                    "drafted_tokens": drafted,
                    "accepted_tokens": accepted,
                    "acceptance_rate": (accepted / drafted if drafted
                                        else 0.0),
                    "mean_accepted_depth": (depth.sum / depth.count
                                            if depth.count else 0.0),
                    "verify_programs": self._verifier.programs,
                    "draft_programs": self._draft.programs}
        return {"id": self.id,
                "kv": kv,
                "spec": spec,
                "slots": self.slots,
                "max_len": self.max_len,
                "precision": self.precision,
                "weight_bytes": tree_bytes(self._weights()[0]),
                "model_version": self._version,
                "occupied_slots": occupied,
                "queued_requests": queued,
                "compiled_programs": self.trace_count,
                "steps": int(self._m_steps.value),
                "tokens": int(toks),
                "requests": int(self._m_requests.value),
                "decode_seconds": self._decode_seconds,
                "tokens_per_second": (toks / self._decode_seconds
                                      if self._decode_seconds else 0.0),
                "slo": self._slo_stats(),
                "journal": {"capacity": self.journal.capacity,
                            "records": len(self.journal),
                            "total": self.journal.total,
                            "dropped": self.journal.dropped},
                "warmup_seconds": self.warmup_seconds}


def generate_naive(model, prompt: Sequence[int], max_new_tokens: int,
                   max_len: int, seed: int = 0, temperature: float = 0.0,
                   top_k: int = 0, _cache={}):
    """Baseline generator: re-runs the FULL prefix forward for every token
    (what serving looks like without decode state) — the bench.py decode
    row's comparison point. Pads to a fixed ``max_len`` so it compiles once,
    and samples with the same fold_in(PRNGKey(seed), position) rule as
    DecodeEngine, so greedy outputs match the engine token-for-token."""
    is_graph = hasattr(model.conf, "network_inputs")
    itype = (model.conf.input_types[0] if is_graph else model.conf.input_type)
    vocab = itype.size

    key = (id(model), max_len)
    step = _cache.get(key)
    if step is None:
        def step(params, state, x, last, seed_, temp, tk):
            if is_graph:
                acts, _, _ = model._forward(params, state, [x],
                                            train=False, rng=None)
                probs = acts[model.conf.network_outputs[0]]
            else:
                probs, _, _ = model._forward(params, state, x,
                                             train=False, rng=None)
            # same oracle as DecodeEngine._step_impl and the speculative
            # verify program — one sampling rule, serving/spec/accept.py
            return oracle_token(jnp.log(probs[0, last]), seed_, last,
                                temp, tk)

        step = _cache[key] = jax.jit(step)

    toks = [int(t) for t in prompt]
    if len(toks) + max_new_tokens > max_len:
        raise ValueError("prompt + max_new_tokens exceeds max_len")
    out = []
    x = np.zeros((1, max_len, vocab), np.float32)
    x[0, np.arange(len(toks)), toks] = 1.0
    for _ in range(max_new_tokens):
        last = len(toks) - 1
        tok = int(step(model.params, model.state, jnp.asarray(x),
                       np.int32(last), np.uint32(seed & 0xFFFFFFFF),
                       np.float32(temperature), np.int32(top_k)))
        out.append(tok)
        x[0, len(toks), tok] = 1.0
        toks.append(tok)
    return {"tokens": out, "prompt_len": len(prompt)}
