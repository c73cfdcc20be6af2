"""A pre-norm sparse decoder language model built from a published
configuration's own keys.

No counterpart in the reference zoo (which tops out at recurrent text
models). The graph is ``EmbeddingSequenceLayer`` in, per layer
``RMSNorm -> RotaryGQAttention -> add -> RMSNorm -> SwiGLU | ExpertLayer ->
add``, a final ``RMSNorm`` and an untied ``RnnOutputLayer`` that takes
integer labels. Nodes of layer i are named ``b<i>.<node>``, so that
``remat='blocks'`` replays one layer at a time.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.zoo.zoo_model import ZooModel


def rotary_settings(config, layer_type):
    """``RotaryGQAttention.rotary`` from ``rope_parameters[layer_type]``."""
    r = config["rope_parameters"][layer_type]
    out = {"theta": r["rope_theta"],
           "dims": int(config["head_dim"]
                       * r.get("partial_rotary_factor", 1))}
    if r.get("rope_type") == "yarn":
        out.update(
            factor=r["factor"],
            original_max_position=r["original_max_position_embeddings"],
            beta_fast=r["beta_fast"], beta_slow=r["beta_slow"],
            attention_factor=r["attention_factor"])
    return out


class SparseDecoder(ZooModel):
    """``config``: a dict with the keys of the model's public
    ``config.json``: ``vocab_size``, ``hidden_size``, ``head_dim``,
    ``num_key_value_heads``, ``rms_norm_eps``, ``sliding_window``,
    ``rope_parameters``, ``gating``, per layer ``layer_types``
    (``full_attention`` | ``sliding_attention``),
    ``num_attention_heads_per_layer`` and ``mlp_layer_types`` (``dense`` |
    ``sparse``), ``intermediate_size``, and for the expert layers
    ``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``shared_expert_intermediate_size``, ``norm_topk_prob``,
    ``moe_routed_scaling_factor``. As many layers are built as
    ``layer_types`` lists. ``experts_held=(count, first)`` gives the
    expert layers a share of the experts (None: all)."""
    name = "sparsedecoder"

    def __init__(self, config, seed: int = 123, experts_held=None, **kwargs):
        kwargs.pop("num_classes", None)
        kwargs.pop("input_shape", None)
        super().__init__(num_classes=config["vocab_size"], seed=seed,
                         input_shape=(config["vocab_size"],), **kwargs)
        self.config = config
        self.experts_held = experts_held

    def conf(self):
        from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingSequenceLayer, RnnOutputLayer, RMSNorm, SwiGLU,
            RotaryGQAttention, ExpertLayer)
        c = self.config
        vocab, hidden, eps = c["vocab_size"], c["hidden_size"], c["rms_norm_eps"]
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed)
             .updater(self.updater(Adam(1e-4)))
             .weight_init("xavier")
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(vocab)))
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=vocab, n_out=hidden, activation="identity"), "tokens")
        prev = "embed"
        for i, kind in enumerate(c["layer_types"]):
            b = f"b{i}"
            g.add_layer(f"{b}.norm1", RMSNorm(eps=eps), prev)
            g.add_layer(f"{b}.attn", RotaryGQAttention(
                n_heads=c["num_attention_heads_per_layer"][i],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                window=(c["sliding_window"] if kind == "sliding_attention"
                        else None),
                rotary=rotary_settings(c, kind),
                head_gate=c.get("gating") == "per-head"), f"{b}.norm1")
            g.add_vertex(f"{b}.add1", ElementWiseVertex(op="add"),
                         f"{b}.attn", prev)
            g.add_layer(f"{b}.norm2", RMSNorm(eps=eps), f"{b}.add1")
            if c["mlp_layer_types"][i] == "dense":
                mlp = SwiGLU(width=c["intermediate_size"])
            else:
                mlp = ExpertLayer(
                    n_experts=c["num_experts"],
                    experts_per_token=c["num_experts_per_tok"],
                    expert_width=c["moe_intermediate_size"],
                    shared_width=c.get("shared_expert_intermediate_size", 0),
                    routed_scale=c.get("moe_routed_scaling_factor", 1.0),
                    norm_topk=c.get("norm_topk_prob", True),
                    experts_held=self.experts_held)
            g.add_layer(f"{b}.mlp", mlp, f"{b}.norm2")
            g.add_vertex(f"{b}.add2", ElementWiseVertex(op="add"),
                         f"{b}.mlp", f"{b}.add1")
            prev = f"{b}.add2"
        g.add_layer("final_norm", RMSNorm(eps=eps), prev)
        g.add_layer("head", RnnOutputLayer(
            n_out=vocab, activation="softmax", loss="mcxent",
            has_bias=False), "final_norm")
        return g.set_outputs("head").build()
