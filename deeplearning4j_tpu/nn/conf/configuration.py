"""Network configuration DSL.

Parity surface: reference NeuralNetConfiguration.Builder
(nn/conf/NeuralNetConfiguration.java:570), MultiLayerConfiguration,
ComputationGraphConfiguration (nn/conf/ComputationGraphConfiguration.java) and
their JSON serde (nn/conf/serde/). The builder carries global hyperparameter
defaults that unset layer fields inherit — same semantics as the reference's
``Builder.layer(...)`` cascade.

Usage:
    conf = (NeuralNetConfiguration.builder()
            .seed(12345)
            .updater(Adam(1e-3))
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=500, activation="relu"))
            .layer(OutputLayer(n_out=10, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
    net = MultiLayerNetwork(conf)
    net.init()
"""

from __future__ import annotations

import json
import copy
import dataclasses
from dataclasses import dataclass, field as dc_field
from typing import Optional, List, Dict, Any, Tuple

from deeplearning4j_tpu.nn.updaters import Updater, Sgd
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict


@dataclass
class GlobalConf:
    """Network-level defaults + training semantics."""
    seed: int = 12345
    activation: str = "sigmoid"          # reference default
    weight_init: str = "xavier"
    dist: Optional[tuple] = None
    bias_init: float = 0.0
    updater: Updater = dc_field(default_factory=lambda: Sgd(1e-3))
    l1: float = 0.0
    l2: float = 0.0
    dropout: object = 0.0                # float drop-prob or IDropout object
    optimization_algo: str = "sgd"       # sgd | lbfgs | line_gradient_descent
    max_num_line_search_iterations: int = 5
    minimize: bool = True
    mini_batch: bool = True
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: float = 1.0
    dtype: str = "float32"               # param dtype
    compute_dtype: Optional[str] = None  # e.g. 'bfloat16' for MXU-friendly fwd/bwd
    # rematerialize activations in the backward pass (jax.checkpoint over
    # the loss). True/'full' recomputes everything; 'save_convs' (alias
    # 'selective') keeps conv outputs and BatchNorm's batch statistics and
    # recomputes only BN's normalisation and the activations.
    # On TPU the conv-net backward is HBM-bound on stored activations: full
    # remat measured up to 5x faster at CIFAR shapes, 'save_convs' won at
    # 224 where conv recompute costs real FLOPs (round-5 ablation, before
    # PR 1; not measured since) — the role cudnn workspace tuning plays in
    # the reference's helper seam
    # 'blocks' (graphs): one jax.checkpoint around each run of nodes named
    # ``<block>.<node>``; a block's input is kept and, of what runs inside
    # it, the values its layers name through util/remat.py:keep (the
    # decoder's: BLOCK_KEPT there); the rest is replayed
    remat: object = False   # False|True|'full'|'save_convs'|'selective'|'blocks'
    weight_noise: Optional[object] = None  # IWeightNoise (DropConnect/...)

    def defaults_dict(self):
        return {"activation": self.activation, "weight_init": self.weight_init,
                "dist": self.dist, "bias_init": self.bias_init,
                "updater": self.updater, "l1": self.l1, "l2": self.l2,
                "dropout": self.dropout, "weight_noise": self.weight_noise}

    def to_dict(self):
        from deeplearning4j_tpu.nn.dropout import IDropout
        wn = self.weight_noise
        do = self.dropout
        plain = dataclasses.replace(
            self, weight_noise=None,
            dropout=0.0 if isinstance(do, IDropout) else do)
        d = dataclasses.asdict(plain)
        d["updater"] = self.updater.to_dict()
        if wn is not None:
            d["weight_noise"] = wn.to_dict()
        if isinstance(do, IDropout):
            d["dropout"] = do.to_dict()
        return d

    @staticmethod
    def from_dict(d):
        d = dict(d)
        d["updater"] = Updater.from_dict(d["updater"])
        if d.get("dist") is not None:
            d["dist"] = tuple(d["dist"])
        if d.get("weight_noise") is not None:
            from deeplearning4j_tpu.nn.weightnoise import IWeightNoise
            d["weight_noise"] = IWeightNoise.from_dict(d["weight_noise"])
        if isinstance(d.get("dropout"), dict):
            from deeplearning4j_tpu.nn.dropout import IDropout
            d["dropout"] = IDropout.from_dict(d["dropout"])
        return GlobalConf(**d)


class NeuralNetConfiguration:
    """Builder entry point (parity: NeuralNetConfiguration.builder())."""

    @staticmethod
    def builder() -> "Builder":
        return Builder()


class Builder:
    def __init__(self):
        self._g = GlobalConf()

    # fluent setters -------------------------------------------------------
    def seed(self, s):
        self._g.seed = int(s); return self

    def activation(self, a):
        self._g.activation = a; return self

    def weight_init(self, w, dist=None):
        self._g.weight_init = w
        if dist is not None:
            self._g.dist = tuple(dist)
        return self

    def dist(self, *d):
        self._g.dist = tuple(d); self._g.weight_init = "distribution"; return self

    def bias_init(self, b):
        self._g.bias_init = float(b); return self

    def updater(self, u: Updater):
        self._g.updater = u; return self

    def learning_rate(self, lr):
        self._g.updater = dataclasses.replace(self._g.updater, learning_rate=lr)
        return self

    def l1(self, v):
        self._g.l1 = float(v); return self

    def l2(self, v):
        self._g.l2 = float(v); return self

    def dropout(self, v):
        """Float drop-probability or an IDropout object
        (Dropout/AlphaDropout/GaussianDropout/GaussianNoise)."""
        from deeplearning4j_tpu.nn.dropout import IDropout
        self._g.dropout = v if isinstance(v, IDropout) else float(v)
        return self

    def optimization_algo(self, a):
        self._g.optimization_algo = str(a).lower(); return self

    def gradient_normalization(self, kind, threshold=1.0):
        self._g.gradient_normalization = kind
        self._g.gradient_normalization_threshold = threshold
        return self

    def dtype(self, dt):
        self._g.dtype = dt; return self

    def compute_dtype(self, dt):
        self._g.compute_dtype = dt; return self

    def remat(self, flag=True):
        from deeplearning4j_tpu.util.remat import check_remat_mode
        self._g.remat = check_remat_mode(flag); return self

    def weight_noise(self, wn):
        """DropConnect / WeightNoise applied to every layer (parity:
        NeuralNetConfiguration.Builder.weightNoise)."""
        self._g.weight_noise = wn; return self

    def mini_batch(self, v):
        self._g.mini_batch = bool(v); return self

    def minimize(self, v=True):
        self._g.minimize = bool(v); return self

    # terminal builders ----------------------------------------------------
    def list(self) -> "ListBuilder":
        return ListBuilder(self._g)

    def graph_builder(self) -> "GraphBuilder":
        from deeplearning4j_tpu.nn.conf.graph_conf import GraphBuilder
        return GraphBuilder(self._g)


class ListBuilder:
    """Parity: NeuralNetConfiguration.ListBuilder → MultiLayerConfiguration."""

    def __init__(self, g: GlobalConf):
        self._g = g
        self._layers: List[Layer] = []
        self._input_type: Optional[InputType] = None
        self._backprop_type = "standard"
        self._tbptt_fwd = 20
        self._tbptt_bwd = 20

    def layer(self, *args):
        """layer(l) or layer(index, l)"""
        if len(args) == 2:
            idx, l = args
            while len(self._layers) <= idx:
                self._layers.append(None)
            self._layers[idx] = l
        else:
            self._layers.append(args[0])
        return self

    def set_input_type(self, it: InputType):
        self._input_type = it; return self

    def backprop_type(self, t, tbptt_fwd=20, tbptt_bwd=20):
        self._backprop_type = t
        self._tbptt_fwd, self._tbptt_bwd = tbptt_fwd, tbptt_bwd
        return self

    def t_bptt_length(self, n):
        self._backprop_type = "tbptt"
        self._tbptt_fwd = self._tbptt_bwd = n
        return self

    def build(self) -> "MultiLayerConfiguration":
        layers = [copy.deepcopy(l) for l in self._layers if l is not None]
        conf = MultiLayerConfiguration(
            global_conf=copy.deepcopy(self._g), layers=layers,
            input_type=self._input_type, backprop_type=self._backprop_type,
            tbptt_fwd_length=self._tbptt_fwd, tbptt_back_length=self._tbptt_bwd)
        conf.finalize()
        return conf


@dataclass
class MultiLayerConfiguration:
    """Sequential net config (parity: MultiLayerConfiguration.java)."""
    global_conf: GlobalConf = dc_field(default_factory=GlobalConf)
    layers: List[Layer] = dc_field(default_factory=list)
    input_type: Optional[InputType] = None
    backprop_type: str = "standard"     # 'standard' | 'tbptt'
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20
    _finalized: bool = False

    def finalize(self):
        """Apply global defaults + run shape inference through the stack
        (parity: MultiLayerConfiguration.setInputType nIn inference +
        preprocessor insertion — here layers handle layout changes natively)."""
        if self._finalized:
            return self
        defaults = self.global_conf.defaults_dict()
        it = self.input_type
        for l in self.layers:
            l.apply_defaults(defaults)
            l.validate()
            if it is not None:
                l.set_n_in(it)
                it = l.output_type(it)
        self._finalized = True
        return self

    def output_types(self) -> List[InputType]:
        it = self.input_type
        outs = []
        for l in self.layers:
            it = l.output_type(it)
            outs.append(it)
        return outs

    # serde ----------------------------------------------------------------
    def to_json(self) -> str:
        return json.dumps({
            "format": "deeplearning4j_tpu/MultiLayerConfiguration/v1",
            "global_conf": self.global_conf.to_dict(),
            "layers": [l.to_dict() for l in self.layers],
            "input_type": self.input_type.to_dict() if self.input_type else None,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
            "finalized": self._finalized,
        }, indent=2)

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        d = json.loads(s)
        conf = MultiLayerConfiguration(
            global_conf=GlobalConf.from_dict(d["global_conf"]),
            layers=[layer_from_dict(ld) for ld in d["layers"]],
            input_type=InputType.from_dict(d["input_type"]) if d.get("input_type") else None,
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20),
        )
        conf._finalized = d.get("finalized", False)
        if not conf._finalized:
            conf.finalize()
        return conf


# re-export for `from ...configuration import ComputationGraphConfiguration`
def __getattr__(name):
    if name == "ComputationGraphConfiguration":
        from deeplearning4j_tpu.nn.conf.graph_conf import ComputationGraphConfiguration
        return ComputationGraphConfiguration
    raise AttributeError(name)
