"""Computation-graph (DAG) configuration + graph vertices.

Parity surface: reference ComputationGraphConfiguration.java (863 LoC),
GraphBuilder, and the vertex set under nn/conf/graph/ + nn/graph/vertex/impl/
(MergeVertex, ElementWiseVertex, SubsetVertex, StackVertex, UnstackVertex,
ScaleVertex, ShiftVertex, L2NormalizeVertex, ReshapeVertex…).

A vertex is a named node with a list of input names; layers are wrapped in an
implicit LayerVertex. Topological execution order is computed once at build
(parity: ComputationGraph.java:394 topo sort) — inside jit the graph is fully
unrolled, so XLA sees one flat fused program.
"""

from __future__ import annotations

import json
import copy
import dataclasses
from dataclasses import dataclass, field as dc_field
from typing import Optional, List, Dict, Any, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict
from deeplearning4j_tpu.nn.conf.configuration import GlobalConf

VERTEX_REGISTRY: Dict[str, type] = {}


def register_vertex(cls):
    VERTEX_REGISTRY[cls.__name__] = cls
    return cls


@dataclass
class GraphVertex:
    """Parameterless function vertex: apply(inputs: list[Array]) -> Array."""

    def apply(self, inputs: List[Any]):
        raise NotImplementedError

    def output_type(self, input_types: List[InputType]) -> InputType:
        return input_types[0]

    def to_dict(self):
        d = dataclasses.asdict(self)
        d["@type"] = type(self).__name__
        return d

    @staticmethod
    def from_dict(d):
        d = dict(d)
        cls = VERTEX_REGISTRY[d.pop("@type")]
        kwargs = {}
        fields = {f.name for f in dataclasses.fields(cls)}
        for k, v in d.items():
            if k in fields:
                kwargs[k] = tuple(v) if isinstance(v, list) else v
        return cls(**kwargs)


@register_vertex
@dataclass
class MergeVertex(GraphVertex):
    """Concat along feature axis (parity: nn/conf/graph/MergeVertex)."""

    def apply(self, inputs):
        return jnp.concatenate(inputs, axis=-1)

    def output_type(self, input_types):
        t0 = input_types[0]
        if t0.kind == "cnn":
            ch = sum(t.channels for t in input_types)
            return InputType.convolutional(t0.height, t0.width, ch)
        if t0.kind == "rnn":
            return InputType.recurrent(sum(t.size for t in input_types),
                                       t0.timeseries_length)
        return InputType.feed_forward(sum(t.flat_size() for t in input_types))


@register_vertex
@dataclass
class ElementWiseVertex(GraphVertex):
    """add | subtract | product | average | max
    (parity: nn/conf/graph/ElementWiseVertex)."""
    op: str = "add"

    def apply(self, inputs):
        out = inputs[0]
        if self.op == "add":
            for x in inputs[1:]:
                out = out + x
        elif self.op == "subtract":
            out = inputs[0] - inputs[1]
        elif self.op == "product":
            for x in inputs[1:]:
                out = out * x
        elif self.op == "average":
            out = sum(inputs) / len(inputs)
        elif self.op == "max":
            for x in inputs[1:]:
                out = jnp.maximum(out, x)
        else:
            raise ValueError(self.op)
        return out


@register_vertex
@dataclass
class SubsetVertex(GraphVertex):
    """Feature-range slice [from, to] inclusive (parity: SubsetVertex)."""
    from_idx: int = 0
    to_idx: int = 0

    def apply(self, inputs):
        return inputs[0][..., self.from_idx:self.to_idx + 1]

    def output_type(self, input_types):
        n = self.to_idx - self.from_idx + 1
        t0 = input_types[0]
        if t0.kind == "rnn":
            return InputType.recurrent(n, t0.timeseries_length)
        return InputType.feed_forward(n)


@register_vertex
@dataclass
class StackVertex(GraphVertex):
    """Stack along batch axis (parity: StackVertex)."""

    def apply(self, inputs):
        return jnp.concatenate(inputs, axis=0)


@register_vertex
@dataclass
class UnstackVertex(GraphVertex):
    """Take slice i of n along batch axis (parity: UnstackVertex)."""
    from_idx: int = 0
    stack_size: int = 1

    def apply(self, inputs):
        x = inputs[0]
        step = x.shape[0] // self.stack_size
        return x[self.from_idx * step:(self.from_idx + 1) * step]


@register_vertex
@dataclass
class ScaleVertex(GraphVertex):
    scale: float = 1.0

    def apply(self, inputs):
        return inputs[0] * self.scale


@register_vertex
@dataclass
class ShiftVertex(GraphVertex):
    shift: float = 0.0

    def apply(self, inputs):
        return inputs[0] + self.shift


@register_vertex
@dataclass
class L2NormalizeVertex(GraphVertex):
    eps: float = 1e-8

    def apply(self, inputs):
        x = inputs[0]
        n = jnp.sqrt((x ** 2).sum(axis=-1, keepdims=True))
        return x / jnp.maximum(n, self.eps)


@register_vertex
@dataclass
class ReshapeVertex(GraphVertex):
    shape: Tuple[int, ...] = ()

    def apply(self, inputs):
        return inputs[0].reshape((inputs[0].shape[0],) + tuple(self.shape))


# ------------------------------------ residual streams (nn/layers/latent.py)

def _hyper_maps(maps, n):
    from deeplearning4j_tpu.nn.layers.latent import _maps_split
    return _maps_split(maps, n)


def _hyper_kernel(x, n):
    """(ops/hyper_connection.py, the interpreter flag) where
    ``latent.hyper_kernels`` routes this pass over ``x`` to its kernels,
    else None (XLA's form)."""
    from deeplearning4j_tpu import ops
    from deeplearning4j_tpu.nn.layers.latent import hyper_kernels
    from deeplearning4j_tpu.ops import hyper_connection
    if hyper_kernels(x, n):
        return hyper_connection, ops.interpret_mode()
    return None


def _rows(a):
    return a.reshape(-1, a.shape[-1])


@register_vertex
@dataclass
class StreamExpand(GraphVertex):
    """(B, T, C) -> (B, T, n*C): the input copied to each of ``streams``
    residual streams."""
    streams: int = 4

    def apply(self, inputs):
        with jax.named_scope("hc"):
            return jnp.tile(inputs[0], (1,) * (inputs[0].ndim - 1)
                            + (self.streams,))

    def output_type(self, input_types):
        t = input_types[0]
        return InputType.recurrent(t.size * self.streams, t.timeseries_length)


@register_vertex
@dataclass
class StreamCollapse(GraphVertex):
    """(B, T, n*C) -> (B, T, C): the sum of the residual streams, in
    float32."""
    streams: int = 4

    def apply(self, inputs):
        x = inputs[0]
        with jax.named_scope("hc"):
            s = x.reshape(*x.shape[:-1], self.streams, -1)
            return s.astype(jnp.float32).sum(axis=-2).astype(x.dtype)

    def output_type(self, input_types):
        t = input_types[0]
        return InputType.recurrent(t.size // self.streams, t.timeseries_length)


@register_vertex
@dataclass
class HyperIn(GraphVertex):
    """[maps, X] -> H_pre X, (B, T, C) in X's dtype: what a sublayer
    reads."""
    streams: int = 4

    def apply(self, inputs):
        maps, x = inputs
        n = self.streams
        with jax.named_scope("hc"), jax.named_scope("hc_mix"):
            pre = _hyper_maps(maps, n)[0]
            kernel = _hyper_kernel(x, n)
            if kernel:
                hc, interpret = kernel
                return hc.mix_in(_rows(x), _rows(pre), interpret).reshape(
                    *x.shape[:-1], -1)
            s = x.reshape(*x.shape[:-1], n, -1).astype(jnp.float32)
            return (pre[..., None] * s).sum(axis=-2).astype(x.dtype)

    def output_type(self, input_types):
        t = input_types[1]
        return InputType.recurrent(t.size // self.streams, t.timeseries_length)


@register_vertex
@dataclass
class HyperOut(GraphVertex):
    """[F, maps, X] -> H_res X + H_post^T F, (B, T, n*C) in X's dtype: the
    residual state after a sublayer that wrote F."""
    streams: int = 4

    def apply(self, inputs):
        f, maps, x = inputs
        n = self.streams
        with jax.named_scope("hc"), jax.named_scope("hc_mix"):
            _, post, res = _hyper_maps(maps, n)
            kernel = _hyper_kernel(x, n)
            if kernel:
                hc, interpret = kernel
                return hc.mix_out(
                    _rows(x), _rows(f), res.reshape(-1, n * n), _rows(post),
                    interpret).reshape(x.shape)
            s = x.reshape(*x.shape[:-1], n, -1).astype(jnp.float32)
            out = jnp.einsum("...ij,...jc->...ic", res, s) \
                + post[..., None] * f.astype(jnp.float32)[..., None, :]
            return out.reshape(x.shape).astype(x.dtype)

    def output_type(self, input_types):
        return input_types[2]


@register_vertex
@dataclass
class NextTokenJoin(GraphVertex):
    """[h, e] -> [h_t | e_{t+1}], (B, T, 2C): the trunk's hidden state beside
    the embedding of the next position's token (zeros at the last
    position, which has none)."""

    def apply(self, inputs):
        h, e = inputs
        with jax.named_scope("mtp"):
            nxt = jnp.pad(e[:, 1:], ((0, 0), (0, 1), (0, 0)))
            return jnp.concatenate([h, nxt.astype(h.dtype)], axis=-1)

    def output_type(self, input_types):
        t = input_types[0]
        return InputType.recurrent(2 * t.size, t.timeseries_length)


@register_vertex
@dataclass
class LastTimeStepVertex(GraphVertex):
    """(B, T, C) → (B, C): the last time step of a sequence, mask-aware.

    Parity: nn/conf/graph/rnn/LastTimeStepVertex.java — the encoder half of
    the CG seq2seq pattern (GravesLSTM → LastTimeStepVertex →
    DuplicateToTimeSeriesVertex → decoder). ``mask_input`` names the network
    input whose (B, T) mask locates each example's true last step; without a
    mask the final step is taken."""
    mask_input: Optional[str] = None

    def apply(self, inputs, mask=None):
        x = inputs[0]
        if mask is None:
            return x[:, -1, :]
        # index of the LAST set mask entry (the reference scans for the last
        # nonzero — a sum would mis-index gapped/non-left-aligned masks)
        T = mask.shape[1]
        idx = T - 1 - jnp.argmax(mask[:, ::-1] > 0, axis=1).astype(jnp.int32)
        idx = jnp.where(jnp.any(mask > 0, axis=1), idx, 0)
        return jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :]

    def output_type(self, input_types):
        return InputType.feed_forward(input_types[0].size)


@register_vertex
@dataclass
class DuplicateToTimeSeriesVertex(GraphVertex):
    """(B, C) → (B, T, C): broadcast a vector across time, T taken from the
    reference sequence named by ``ref_input`` (parity:
    nn/conf/graph/rnn/DuplicateToTimeSeriesVertex.java — the decoder-seeding
    half of the CG seq2seq pattern). ``ref_input`` is appended to the
    vertex's graph inputs at add time, so topo order and serde carry it."""
    ref_input: Optional[str] = None

    def apply(self, inputs):
        x, ref = inputs[0], inputs[-1]
        return jnp.broadcast_to(x[:, None, :],
                                (x.shape[0], ref.shape[1], x.shape[1]))

    def output_type(self, input_types):
        t0, tref = input_types[0], input_types[-1]
        return InputType.recurrent(t0.flat_size(),
                                   getattr(tref, "timeseries_length", None))


@register_vertex
@dataclass
class L2Vertex(GraphVertex):
    """Pairwise L2 distance between two activations → (B, 1)
    (parity: nn/conf/graph/L2Vertex.java)."""
    eps: float = 1e-8

    def apply(self, inputs):
        d = inputs[0] - inputs[1]
        ss = (d * d).sum(axis=tuple(range(1, d.ndim)))
        return jnp.sqrt(jnp.maximum(ss, self.eps))[:, None]

    def output_type(self, input_types):
        return InputType.feed_forward(1)


@register_vertex
@dataclass
class PreprocessorVertex(GraphVertex):
    """Shape-transform vertex (parity: nn/conf/graph/PreprocessorVertex.java
    wrapping the InputPreProcessor impls). Named transforms over this build's
    native layouts (NHWC images, (B, T, C) sequences):

    - ``cnn_to_ff``: (B, H, W, C) → (B, H·W·C)
    - ``ff_to_cnn``: (B, H·W·C) → (B, height, width, channels) [fields]
    - ``rnn_to_ff``: (B, T, C) → (B·T, C)
    - ``ff_to_rnn``: (B·T, C) → (B, tsteps, C) [field]
    """
    preprocessor: str = "cnn_to_ff"
    height: int = 0
    width: int = 0
    channels: int = 0
    tsteps: int = 0

    def apply(self, inputs):
        x = inputs[0]
        if self.preprocessor == "cnn_to_ff":
            return x.reshape(x.shape[0], -1)
        if self.preprocessor == "ff_to_cnn":
            return x.reshape(x.shape[0], self.height, self.width,
                             self.channels)
        if self.preprocessor == "rnn_to_ff":
            return x.reshape(x.shape[0] * x.shape[1], x.shape[2])
        if self.preprocessor == "ff_to_rnn":
            return x.reshape(x.shape[0] // self.tsteps, self.tsteps,
                             x.shape[1])
        raise ValueError(f"Unknown preprocessor '{self.preprocessor}'")

    def output_type(self, input_types):
        t = input_types[0]
        if self.preprocessor == "cnn_to_ff":
            return InputType.feed_forward(t.flat_size())
        if self.preprocessor == "ff_to_cnn":
            return InputType.convolutional(self.height, self.width,
                                           self.channels)
        if self.preprocessor == "rnn_to_ff":
            return InputType.feed_forward(t.size)
        if self.preprocessor == "ff_to_rnn":
            return InputType.recurrent(t.flat_size(), self.tsteps)
        raise ValueError(f"Unknown preprocessor '{self.preprocessor}'")


@register_vertex
@dataclass
class PoolHelperVertex(GraphVertex):
    """Crops first row/col (parity: zoo GoogLeNet's PoolHelperVertex)."""

    def apply(self, inputs):
        return inputs[0][:, 1:, 1:, :]

    def output_type(self, input_types):
        t = input_types[0]
        return InputType.convolutional(t.height - 1, t.width - 1, t.channels)


@dataclass
class _Node:
    name: str
    kind: str                     # 'input' | 'layer' | 'vertex'
    layer: Optional[Layer] = None
    vertex: Optional[GraphVertex] = None
    inputs: List[str] = dc_field(default_factory=list)


@dataclass
class ComputationGraphConfiguration:
    """DAG net config (parity: ComputationGraphConfiguration.java)."""
    global_conf: GlobalConf = dc_field(default_factory=GlobalConf)
    nodes: Dict[str, _Node] = dc_field(default_factory=dict)
    network_inputs: List[str] = dc_field(default_factory=list)
    network_outputs: List[str] = dc_field(default_factory=list)
    input_types: Optional[List[InputType]] = None
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    topological_order: List[str] = dc_field(default_factory=list)

    def topo_sort(self):
        """Kahn's algorithm (parity: ComputationGraph.java:394)."""
        indeg = {n: 0 for n in self.nodes}
        children: Dict[str, List[str]] = {n: [] for n in self.nodes}
        for name, node in self.nodes.items():
            for inp in node.inputs:
                if inp not in self.nodes:
                    raise ValueError(f"Vertex '{name}' references unknown input '{inp}'")
                indeg[name] += 1
                children[inp].append(name)
        queue = [n for n, d in sorted(indeg.items()) if d == 0]
        order = []
        while queue:
            n = queue.pop(0)
            order.append(n)
            for c in children[n]:
                indeg[c] -= 1
                if indeg[c] == 0:
                    queue.append(c)
        if len(order) != len(self.nodes):
            cyc = [n for n, d in indeg.items() if d > 0]
            raise ValueError(f"Graph has a cycle involving {cyc}")
        self.topological_order = order
        return order

    def finalize(self):
        defaults = self.global_conf.defaults_dict()
        self.topo_sort()
        # shape inference through topo order
        types: Dict[str, InputType] = {}
        if self.input_types:
            for n, t in zip(self.network_inputs, self.input_types):
                types[n] = t
        for name in self.topological_order:
            node = self.nodes[name]
            if node.kind == "input":
                continue
            in_types = [types.get(i) for i in node.inputs]
            if node.kind == "layer":
                node.layer.apply_defaults(defaults)
                node.layer.validate()
                if in_types and in_types[0] is not None:
                    node.layer.set_n_in(in_types[0])
                    types[name] = node.layer.output_type(in_types[0])
            else:
                if all(t is not None for t in in_types) and in_types:
                    types[name] = node.vertex.output_type(in_types)
        self.node_output_types = types
        return self

    # serde ----------------------------------------------------------------
    def to_json(self):
        return json.dumps({
            "format": "deeplearning4j_tpu/ComputationGraphConfiguration/v1",
            "global_conf": self.global_conf.to_dict(),
            "nodes": [{
                "name": n.name, "kind": n.kind,
                "layer": n.layer.to_dict() if n.layer else None,
                "vertex": n.vertex.to_dict() if n.vertex else None,
                "inputs": n.inputs,
            } for n in self.nodes.values()],
            "network_inputs": self.network_inputs,
            "network_outputs": self.network_outputs,
            "input_types": [t.to_dict() for t in self.input_types] if self.input_types else None,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }, indent=2)

    @staticmethod
    def from_json(s):
        d = json.loads(s)
        conf = ComputationGraphConfiguration(
            global_conf=GlobalConf.from_dict(d["global_conf"]),
            network_inputs=d["network_inputs"],
            network_outputs=d["network_outputs"],
            input_types=[InputType.from_dict(t) for t in d["input_types"]]
            if d.get("input_types") else None,
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )
        for nd in d["nodes"]:
            conf.nodes[nd["name"]] = _Node(
                name=nd["name"], kind=nd["kind"],
                layer=layer_from_dict(nd["layer"]) if nd.get("layer") else None,
                vertex=GraphVertex.from_dict(nd["vertex"]) if nd.get("vertex") else None,
                inputs=nd.get("inputs", []))
        conf.finalize()
        return conf


class GraphBuilder:
    """Parity: ComputationGraphConfiguration.GraphBuilder."""

    def __init__(self, g: GlobalConf):
        self._conf = ComputationGraphConfiguration(global_conf=copy.deepcopy(g))

    def add_inputs(self, *names):
        for n in names:
            self._conf.network_inputs.append(n)
            self._conf.nodes[n] = _Node(name=n, kind="input")
        return self

    def set_input_types(self, *types):
        self._conf.input_types = list(types)
        return self

    def add_layer(self, name: str, layer: Layer, *inputs: str):
        layer = copy.deepcopy(layer)
        layer.name = name
        self._conf.nodes[name] = _Node(name=name, kind="layer", layer=layer,
                                       inputs=list(inputs))
        return self

    def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str):
        inputs = list(inputs)
        ref = getattr(vertex, "ref_input", None)
        if ref and ref not in inputs:
            # DuplicateToTimeSeriesVertex's reference sequence is a real data
            # dependency: wire it so topo sort orders it and apply() sees it
            inputs.append(ref)
        self._conf.nodes[name] = _Node(name=name, kind="vertex", vertex=vertex,
                                       inputs=inputs)
        return self

    def set_outputs(self, *names):
        self._conf.network_outputs = list(names)
        return self

    def backprop_type(self, t, tbptt_fwd=20, tbptt_bwd=20):
        self._conf.backprop_type = t
        self._conf.tbptt_fwd_length = tbptt_fwd
        self._conf.tbptt_back_length = tbptt_bwd
        return self

    def build(self):
        return self._conf.finalize()
