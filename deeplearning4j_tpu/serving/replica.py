"""Replica workers for the routed serving tier (docs/SERVING_TIER.md).

A *replica* is one PR-4/PR-5 ``InferenceServer`` (micro-batched /predict,
optional DecodeEngine /generate) that the ``Router`` fronts. This module
supplies the three ways a replica exists:

- ``main()`` — the subprocess entrypoint
  (``python -m deeplearning4j_tpu.serving.replica --model charlstm ...``):
  builds a small deterministic model, serves it, writes its bound port to
  ``--port-file`` so the parent can find an OS-assigned port, drains
  gracefully on SIGTERM, and optionally mounts the chaos surface
  (``--chaos`` → resilience.faults.ServerFaultInjector behind
  ``POST /chaos``).
- ``ReplicaProcess`` — the parent-side handle: Popen + wait_ready() +
  stop() (SIGTERM, graceful) + kill() (SIGKILL, the chaos soak's crash) +
  start() again on the SAME port (restart-in-place for rolling deploys).
- ``InProcessReplica`` — an in-process InferenceServer with the same
  handle shape, for router tests where process isolation adds nothing but
  seconds.

Models are intentionally tiny: replicas must cold-start (including XLA
compiles) in seconds on a CPU test box, because the chaos harness
restarts them mid-test. The persistent compile cache makes second and
later starts near-instant.
"""

from __future__ import annotations

import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

__all__ = ["ReplicaProcess", "InProcessReplica", "build_model",
           "build_server", "main"]

# charlstm vocab — small so one decode step is microseconds on CPU
CHAR_VOCAB = 16


def build_model(name: str):
    """Deterministic tiny models (fixed seeds: every replica of a tier has
    bit-identical params, so failover parity is testable)."""
    from deeplearning4j_tpu import MultiLayerNetwork, NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.layers import (DenseLayer, LSTM, OutputLayer,
                                              RnnOutputLayer)
    from deeplearning4j_tpu.nn.updaters import Adam
    if name == "mlp":
        conf = (NeuralNetConfiguration.builder().seed(42).updater(Adam(1e-2))
                .weight_init("xavier").list()
                .layer(DenseLayer(n_out=16, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(4))
                .build())
        return MultiLayerNetwork(conf).init()
    if name == "widemlp":
        # comms-heavy variant of "mlp" (same 4-feature task, ~13 MB of
        # f32 params) — big enough that the elastic bench's gradient
        # exchange dominates a step, which is what the chain-vs-star
        # throughput comparison needs to measure
        conf = (NeuralNetConfiguration.builder().seed(42).updater(Adam(1e-2))
                .weight_init("xavier").list()
                .layer(DenseLayer(n_out=1024, activation="relu"))
                .layer(DenseLayer(n_out=2048, activation="relu"))
                .layer(DenseLayer(n_out=512, activation="relu"))
                .layer(OutputLayer(n_out=3, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.feed_forward(4))
                .build())
        return MultiLayerNetwork(conf).init()
    if name == "charlstm":
        conf = (NeuralNetConfiguration.builder().seed(42).updater(Adam(1e-2))
                .weight_init("xavier").list()
                .layer(LSTM(n_out=24, activation="tanh"))
                .layer(RnnOutputLayer(n_out=CHAR_VOCAB, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(CHAR_VOCAB))
                .build())
        return MultiLayerNetwork(conf).init()
    if name == "charlstm-draft":
        # the speculative draft for charlstm: same vocabulary, one narrow
        # LSTM — a draft step must cost a fraction of a target step, and
        # the seed differs so draft/target never share weights
        conf = (NeuralNetConfiguration.builder().seed(17).updater(Adam(1e-2))
                .weight_init("xavier").list()
                .layer(LSTM(n_out=8, activation="tanh"))
                .layer(RnnOutputLayer(n_out=CHAR_VOCAB, activation="softmax",
                                      loss="mcxent"))
                .set_input_type(InputType.recurrent(CHAR_VOCAB))
                .build())
        return MultiLayerNetwork(conf).init()
    if name == "tinyattn":
        # attention-only decode state: the model disaggregated-serving
        # tests and benches need — paged KV with prefix_cache works (no
        # recurrent carries), so chains can be cached, migrated between
        # replicas, and spilled to the host tier. Same vocabulary as
        # charlstm so the fleet fixtures reuse their prompt generators.
        from deeplearning4j_tpu.zoo.simple import TinyTransformer
        return TinyTransformer(vocab_size=CHAR_VOCAB, n_layers=2,
                               d_model=32, n_heads=4, max_len=256,
                               seed=42).init()
    raise ValueError(
        f"unknown replica model {name!r} "
        f"(mlp | widemlp | charlstm | charlstm-draft | tinyattn)")


def build_server(model_name: str = "charlstm", port: int = 0,
                 slots: int = 4, max_len: int = 64, max_queue: int = 256,
                 max_latency_ms: float = 2.0, chaos: bool = False,
                 precision: Optional[str] = None, kv: str = "dense",
                 kv_block_size: int = 16, kv_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 chunk_tokens: Optional[int] = None,
                 spec_draft: Optional[str] = None, spec_k: int = 4,
                 spec_tree: Optional[str] = None,
                 spec_self_draft: Optional[str] = None,
                 role: str = "mixed",
                 host_kv_bytes: Optional[int] = None,
                 journal_capacity: int = 512):
    """Assemble (but don't start) a replica InferenceServer. ``charlstm``
    serves both /predict and /generate; ``mlp`` is predict-only.
    ``precision`` (None = the executor policy / DL4JTPU_PRECISION) puts
    BOTH engines on the low-precision serving path — boot-time
    ``--checkpoint`` swaps and later /admin/swap deploys arrive in f32
    and quantize behind the validation gate (docs/QUANTIZATION.md).
    ``kv``/``kv_block_size``/``kv_blocks``/``prefix_cache``/
    ``chunk_tokens`` select the paged KV cache for the decode engine
    (docs/DECODING.md "Paged KV"); ``prefix_cache`` defaults off here
    because the stock charlstm carries recurrent decode state, which the
    prefix cache cannot share. ``spec_draft`` names a draft model (e.g.
    ``charlstm-draft``) — or ``spec_self_draft`` reuses the target's own
    weights (``int8``/``fp8``/``early_exit:M``, no extra checkpoint) —
    to switch /generate to speculative decoding: ``spec_k`` tokens per
    tick, or a branching token tree with ``spec_tree`` ("3,2,2" =
    branching factors per depth); output stays bitwise-identical to the
    plain engine (docs/DECODING.md "Tree speculation & self-drafting").
    ``tinyattn`` (attention-only decode state) serves /generate with
    full paged-KV features: prefix_cache, /kv/export + /kv/import
    migration, and — with ``host_kv_bytes`` — the host-memory KV tier.
    ``role`` declares the replica's disaggregation specialization
    (prefill | decode | mixed), advertised via /stats for the router's
    role-aware placement. ``journal_capacity`` bounds the wide-event
    request journals (predict + decode) served at ``GET /requests``."""
    from deeplearning4j_tpu.serving.decode import DecodeEngine
    from deeplearning4j_tpu.serving.engine import InferenceEngine
    from deeplearning4j_tpu.serving.server import InferenceServer
    net = build_model(model_name)
    eng = InferenceEngine(net, precision=precision)
    dec = None
    if model_name in ("charlstm", "tinyattn"):
        spec = None
        if spec_draft is not None or spec_self_draft is not None:
            from deeplearning4j_tpu.serving.spec import (SpecConfig,
                                                         parse_kvec)
            spec = SpecConfig(
                build_model(spec_draft) if spec_draft is not None else None,
                k=spec_k,
                tree=(parse_kvec(spec_tree) if spec_tree is not None
                      else None),
                self_draft=spec_self_draft)
        dec = DecodeEngine(net, slots=slots, max_len=max_len,
                           max_queue=max_queue, precision=precision,
                           kv=kv, kv_block_size=kv_block_size,
                           kv_blocks=kv_blocks, prefix_cache=prefix_cache,
                           chunk_tokens=chunk_tokens,
                           host_kv_bytes=host_kv_bytes, spec=spec,
                           journal_capacity=journal_capacity)
    injector = None
    if chaos:
        from deeplearning4j_tpu.resilience.faults import ServerFaultInjector
        injector = ServerFaultInjector()
    return InferenceServer(net, port=port, max_latency_ms=max_latency_ms,
                           max_queue=max_queue, engine=eng,
                           decode_engine=dec, fault_injector=injector,
                           role=role, journal_capacity=journal_capacity)


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="dl4jtpu serving replica worker")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--port-file", default=None,
                        help="write the bound port here once listening")
    parser.add_argument("--model", default="charlstm",
                        choices=("mlp", "charlstm", "tinyattn"))
    parser.add_argument("--role", default="mixed",
                        choices=("prefill", "decode", "mixed"),
                        help="disaggregation role advertised in /stats: "
                             "the router prefers prefill/mixed replicas "
                             "for fresh prefills and steers shared-prefix "
                             "fan-out by chain affinity")
    parser.add_argument("--host-kv-bytes", type=int, default=None,
                        help="host-memory KV tier byte budget (paged + "
                             "--prefix-cache only): evicted prefix blocks "
                             "spill to host RAM and restore on later hits")
    parser.add_argument("--slots", type=int, default=4)
    parser.add_argument("--max-len", type=int, default=64)
    parser.add_argument("--max-queue", type=int, default=256)
    parser.add_argument("--max-latency-ms", type=float, default=2.0)
    parser.add_argument("--journal-capacity", type=int, default=512,
                        help="wide-event request journal ring size per "
                             "engine (GET /requests); oldest dropped first")
    parser.add_argument("--chaos", action="store_true",
                        help="mount POST /chaos (test-only fault injection)")
    parser.add_argument("--warmup", action="store_true",
                        help="pre-compile before accepting traffic")
    parser.add_argument("--aot", default=None,
                        help="AOT artifact path (exec/aot.py): restore "
                             "serialized executables instead of retracing, "
                             "trace-and-save on any miss (implies warmup)")
    parser.add_argument("--checkpoint", default=None,
                        help="swap in the weights of this checkpoint zip "
                             "before accepting traffic (restart from a "
                             "promoted online-learning checkpoint)")
    parser.add_argument("--precision", default=None,
                        choices=("f32", "int8", "fp8"),
                        help="serving precision for both engines (default: "
                             "the executor policy / DL4JTPU_PRECISION)")
    parser.add_argument("--trace", action="store_true",
                        help="enable span tracing (also via DL4JTPU_TRACE); "
                             "the ring buffer is served at GET /trace for "
                             "fleet collection")
    parser.add_argument("--kv", default="dense", choices=("dense", "paged"),
                        help="decode KV layout: per-slot dense caches or "
                             "the block-pool paged cache")
    parser.add_argument("--kv-block-size", type=int, default=16,
                        help="tokens per KV block (paged only; must divide "
                             "--max-len)")
    parser.add_argument("--kv-blocks", type=int, default=None,
                        help="KV pool size in blocks (paged only; default "
                             "sizes for full slot occupancy)")
    parser.add_argument("--prefix-cache", action="store_true",
                        help="reuse completed prefill blocks across "
                             "requests sharing a prompt prefix (paged only; "
                             "needs a model with no recurrent decode state)")
    parser.add_argument("--chunk-tokens", type=int, default=None,
                        help="split prefill into chunks of this many tokens "
                             "riding the batched decode cadence (paged only)")
    parser.add_argument("--spec-draft", default=None,
                        choices=("charlstm-draft",),
                        help="speculative decoding: draft model name for "
                             "the decode engine (lossless — output is "
                             "bitwise the non-speculative stream)")
    parser.add_argument("--spec-k", type=int, default=4,
                        help="tokens the draft proposes per tick "
                             "(with --spec-draft)")
    parser.add_argument("--spec-tree", default=None,
                        help="tree speculation: branching factors per "
                             "depth, e.g. '3,2,2' (overrides --spec-k; "
                             "the draft's trajectory is the spine, "
                             "top-logit alternatives fill the branches)")
    parser.add_argument("--spec-self-draft", default=None,
                        help="self-drafting: the target as its own draft "
                             "— 'int8' / 'fp8' (quantized) or "
                             "'early_exit:M' (first M layers + readout); "
                             "replaces --spec-draft, no extra checkpoint")
    args = parser.parse_args(argv)

    # the process runs on whatever backend its environment gives it, and
    # says which in its ready line and in /stats
    from deeplearning4j_tpu.util.compile_cache import setup_compile_cache
    setup_compile_cache()       # restart-in-place must not recompile

    from deeplearning4j_tpu.monitor import trace as _trace
    if args.trace:
        _trace.enable(True)

    srv = build_server(args.model, port=args.port, slots=args.slots,
                       max_len=args.max_len, max_queue=args.max_queue,
                       max_latency_ms=args.max_latency_ms, chaos=args.chaos,
                       precision=args.precision, kv=args.kv,
                       kv_block_size=args.kv_block_size,
                       kv_blocks=args.kv_blocks,
                       prefix_cache=args.prefix_cache,
                       chunk_tokens=args.chunk_tokens,
                       spec_draft=args.spec_draft, spec_k=args.spec_k,
                       spec_tree=args.spec_tree,
                       spec_self_draft=args.spec_self_draft,
                       role=args.role, host_kv_bytes=args.host_kv_bytes,
                       journal_capacity=args.journal_capacity)
    # warmup BEFORE the serve loops start so REPLICA_READY / the port-file
    # handshake mean genuinely ready-to-serve: with --aot this is a
    # millisecond restore, without it the full trace-and-save
    if srv.decode_engine is not None:
        if args.warmup or args.aot:
            srv.decode_engine.warmup(aot=args.aot)
        srv.decode_engine.start()
    if (args.warmup or args.aot) and args.model == "mlp":
        srv.engine.warmup((4,), max_batch=64, aot=args.aot)
    srv.start()
    if args.checkpoint:
        # boot-time deploy of a promoted checkpoint: the replica starts from
        # its deterministic seed weights and swaps (zero extra compiles,
        # same shapes) rather than deserialising a whole different conf
        v = srv.swap_checkpoint(args.checkpoint)
        print(f"REPLICA_SWAPPED version={v} "
              f"checkpoint={args.checkpoint}", flush=True)

    stopping = []

    def _sigterm(signum, frame):
        # graceful drain: in-flight requests finish, /healthz flips to
        # draining, then the process exits 0
        stopping.append(True)

    signal.signal(signal.SIGTERM, _sigterm)

    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(str(srv.port))
        os.replace(tmp, args.port_file)      # atomic: parent never reads ""
    # name this process's track in merged fleet traces
    _trace.set_process_name(f"replica:{args.model}@{srv.port}")
    from deeplearning4j_tpu.exec.mesh import device_info
    dev = device_info()
    print(f"REPLICA_READY port={srv.port} pid={os.getpid()} "
          f"model={args.model} platform={dev['platform']} "
          f"kind={dev['kind']!r} devices={dev['count']}", flush=True)

    try:
        while not stopping:
            time.sleep(0.05)
    except KeyboardInterrupt:
        pass
    srv.stop()
    if srv.decode_engine is not None:
        srv.decode_engine.stop()
    print("REPLICA_STOPPED", flush=True)
    return 0


def _repo_root() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.dirname(os.path.dirname(here))


class ReplicaProcess:
    """Parent-side handle for a subprocess replica.

        rep = ReplicaProcess(workdir, model="charlstm").start().wait_ready()
        ... rep.url ...
        rep.kill()          # SIGKILL: the crash the router must absorb
        rep.start().wait_ready()   # restart-in-place, same port

    The first ``start()`` lets the OS pick a port (read back through
    ``--port-file``); later starts reuse it so the router's upstream URL
    stays valid across restarts (allow_reuse_address makes the rebind
    race-free)."""

    def __init__(self, workdir: str, model: str = "charlstm",
                 slots: int = 4, max_len: int = 64,
                 chaos: bool = True, warmup: bool = True,
                 name: str = "replica", checkpoint: Optional[str] = None,
                 precision: Optional[str] = None, trace: bool = False,
                 kv: str = "dense", kv_block_size: int = 16,
                 kv_blocks: Optional[int] = None, prefix_cache: bool = False,
                 chunk_tokens: Optional[int] = None,
                 spec_draft: Optional[str] = None, spec_k: int = 4,
                 spec_tree: Optional[str] = None,
                 spec_self_draft: Optional[str] = None,
                 role: str = "mixed",
                 host_kv_bytes: Optional[int] = None,
                 aot: Optional[str] = None,
                 env: Optional[dict] = None):
        self.workdir = workdir
        self.model = model
        self.slots = slots
        self.max_len = max_len
        self.chaos = chaos
        self.warmup = warmup
        self.name = name
        self.precision = precision
        self.kv = kv
        self.kv_block_size = kv_block_size
        self.kv_blocks = kv_blocks
        self.prefix_cache = prefix_cache
        self.chunk_tokens = chunk_tokens
        self.spec_draft = spec_draft
        self.spec_k = spec_k
        self.spec_tree = spec_tree
        self.spec_self_draft = spec_self_draft
        self.role = role
        self.host_kv_bytes = host_kv_bytes
        # span tracing in the child (GET /trace serves its ring buffer)
        self.trace = trace
        # mutable: rolling restarts set this to the latest promoted
        # checkpoint so a restarted replica boots on current weights
        self.checkpoint = checkpoint
        # AOT artifact for instant cold-start; extra child env
        self.aot = aot
        self.extra_env = env
        # spawn → port-file → first healthy probe, set by wait_ready()
        self.ready_seconds: Optional[float] = None
        self._t_spawn: Optional[float] = None
        self.port: Optional[int] = None
        self.proc: Optional[subprocess.Popen] = None
        self._log = os.path.join(workdir, f"{name}.log")
        self._port_file = os.path.join(workdir, f"{name}.port")

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def start(self) -> "ReplicaProcess":
        """Spawn the replica PINNED TO THE CPU (``JAX_PLATFORMS=cpu`` in
        the child): an accelerator belongs to one process at a time, and
        the spawning parent may already hold it. The child says so in
        its ready line and under ``device`` in ``/stats``; no figure
        taken through a ``ReplicaProcess`` is a chip figure."""
        if os.path.exists(self._port_file) and self.port is None:
            os.unlink(self._port_file)
        cmd = [sys.executable, "-m", "deeplearning4j_tpu.serving.replica",
               "--model", self.model, "--slots", str(self.slots),
               "--max-len", str(self.max_len),
               "--port", str(self.port or 0),
               "--port-file", self._port_file]
        if self.chaos:
            cmd.append("--chaos")
        if self.warmup:
            cmd.append("--warmup")
        if self.checkpoint:
            cmd.extend(["--checkpoint", os.fspath(self.checkpoint)])
        if self.precision:
            cmd.extend(["--precision", self.precision])
        if self.trace:
            cmd.append("--trace")
        if self.kv != "dense":
            cmd.extend(["--kv", self.kv,
                        "--kv-block-size", str(self.kv_block_size)])
            if self.kv_blocks is not None:
                cmd.extend(["--kv-blocks", str(self.kv_blocks)])
            if self.prefix_cache:
                cmd.append("--prefix-cache")
            if self.chunk_tokens is not None:
                cmd.extend(["--chunk-tokens", str(self.chunk_tokens)])
            if self.host_kv_bytes is not None:
                cmd.extend(["--host-kv-bytes", str(self.host_kv_bytes)])
        if self.role != "mixed":
            cmd.extend(["--role", self.role])
        if self.spec_draft is not None:
            cmd.extend(["--spec-draft", self.spec_draft,
                        "--spec-k", str(self.spec_k)])
        if self.spec_tree is not None:
            cmd.extend(["--spec-tree", self.spec_tree])
        if self.spec_self_draft is not None:
            cmd.extend(["--spec-self-draft", self.spec_self_draft])
        if self.aot:
            cmd.extend(["--aot", os.fspath(self.aot)])
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = (_repo_root() + os.pathsep
                             + env.get("PYTHONPATH", ""))
        if self.extra_env:
            env.update(self.extra_env)
        # log to a FILE: a full stdout pipe would deadlock a replica that
        # nobody is reading, and post-mortems want the log anyway
        self._logf = open(self._log, "ab")
        self._t_spawn = time.monotonic()
        self.proc = subprocess.Popen(cmd, stdout=self._logf,
                                     stderr=subprocess.STDOUT, env=env,
                                     cwd=self.workdir)
        return self

    def wait_ready(self, timeout: float = 180.0) -> "ReplicaProcess":
        """Block until the replica's /healthz answers ok (covers the
        port-file handshake AND warmup compiles)."""
        from deeplearning4j_tpu.serving.client import InferenceClient
        deadline = time.monotonic() + timeout
        while self.port is None:
            if os.path.exists(self._port_file):
                with open(self._port_file) as f:
                    text = f.read().strip()
                if text:
                    self.port = int(text)
                    break
            if self.proc is not None and self.proc.poll() is not None:
                raise RuntimeError(
                    f"replica {self.name} exited rc={self.proc.returncode} "
                    f"before binding; see {self._log}")
            if time.monotonic() >= deadline:
                raise TimeoutError(
                    f"replica {self.name} never wrote {self._port_file}")
            time.sleep(0.05)
        cli = InferenceClient(self.url, timeout=5.0, retries=1)
        try:
            while True:
                try:
                    if cli.health().get("status") == "ok":
                        self._note_ready()
                        return self
                except Exception:   # noqa: BLE001 — still booting
                    pass
                if self.proc is not None and self.proc.poll() is not None:
                    raise RuntimeError(
                        f"replica {self.name} exited rc="
                        f"{self.proc.returncode} during boot; "
                        f"see {self._log}")
                if time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"replica {self.name} on port {self.port} never "
                        f"became healthy")
                time.sleep(0.05)
        finally:
            cli.close()

    def _note_ready(self) -> None:
        """Record spawn → first healthy probe: the per-replica cold-start
        the autoscaler amortizes (``dl4jtpu_replica_ready_seconds``)."""
        if self._t_spawn is None:
            return
        self.ready_seconds = time.monotonic() - self._t_spawn
        self._t_spawn = None
        try:
            from deeplearning4j_tpu.monitor import get_registry
            get_registry().histogram(
                "dl4jtpu_replica_ready_seconds",
                "Wall seconds from process spawn through the port-file "
                "handshake to the first healthy /healthz probe — the "
                "cold-start the AOT artifact shrinks.",
                ("replica",)).labels(replica=self.name).observe(
                    self.ready_seconds)
        except Exception:   # noqa: BLE001 — telemetry must not fail boot
            pass

    def stop(self, timeout: float = 30.0) -> None:
        """SIGTERM → graceful drain → exit 0."""
        if self.proc is None or self.proc.poll() is not None:
            return
        self.proc.send_signal(signal.SIGTERM)
        try:
            self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=10)
        self._close_log()

    def kill(self) -> None:
        """SIGKILL: no drain, no flushed sockets — the genuine crash."""
        if self.proc is None:
            return
        try:
            os.kill(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait(timeout=10)
        self._close_log()

    def _close_log(self) -> None:
        logf = getattr(self, "_logf", None)
        if logf is not None:
            try:
                logf.close()
            except OSError:
                pass
            self._logf = None


class InProcessReplica:
    """Same handle shape as ReplicaProcess, backed by an in-process
    InferenceServer — for router tests where subprocess isolation adds
    only wall-clock. NOTE: in-process replicas share the process-global
    metrics registry with the router; series stay distinguishable through
    their labels.

    ``restart()`` stops the server (graceful drain) and starts a fresh one
    on the SAME port — the restarter hook ``Router.rolling_restart`` wants.
    """

    def __init__(self, model: str = "mlp", chaos: bool = True, **server_kw):
        self.model = model
        self.chaos = chaos
        self.server_kw = server_kw
        self.srv = None
        self.port: Optional[int] = None

    @property
    def url(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    @property
    def fault_injector(self):
        return self.srv.fault_injector if self.srv else None

    def start(self) -> "InProcessReplica":
        self.srv = build_server(self.model, port=self.port or 0,
                                chaos=self.chaos, **self.server_kw)
        if self.srv.decode_engine is not None:
            self.srv.decode_engine.start()
        self.srv.start()
        self.port = self.srv.port
        return self

    def wait_ready(self, timeout: float = 180.0) -> "InProcessReplica":
        """No-op for handle parity: start() returns already listening."""
        return self

    def stop(self) -> None:
        if self.srv is not None:
            srv, self.srv = self.srv, None
            srv.stop()
            if srv.decode_engine is not None:
                srv.decode_engine.stop()

    def restart(self) -> None:
        self.stop()
        self.start()


if __name__ == "__main__":
    sys.exit(main())
