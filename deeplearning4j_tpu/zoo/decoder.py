"""A pre-norm sparse decoder language model built from a published
configuration's own keys.

No counterpart in the reference zoo (which tops out at recurrent text
models). The graph is ``EmbeddingSequenceLayer`` in, the layers, a final
``RMSNorm`` and an untied ``RnnOutputLayer`` that takes integer labels. A
layer is an attention + MLP pair, ``RMSNorm -> RotaryGQAttention -> add ->
RMSNorm -> SwiGLU | ExpertLayer -> add``, or, in a hybrid stack, one mixer
alone, ``RMSNorm -> Mamba2Mixer | ExpertLayer | RotaryGQAttention -> add``.
Nodes of layer i are named ``b<i>.<node>``, so that ``remat='blocks'``
replays one layer at a time.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.zoo.zoo_model import ZooModel


def rotary_settings(config, layer_type):
    """``RotaryGQAttention.rotary`` from ``rope_parameters[layer_type]``,
    or from the scalar ``rope_theta`` / ``rope_scaling`` of a configuration
    whose layers are all of one kind. ``mrope_section`` (rotary frequencies
    shared out over position axes) is plain rotary while the axes are equal,
    as they are for text."""
    if "rope_parameters" not in config:
        r = dict(config.get("rope_scaling") or {},
                 rope_theta=config["rope_theta"])
    else:
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
    ``num_key_value_heads``, ``rms_norm_eps``, ``intermediate_size``, and
    for the expert layers ``num_experts``, ``num_experts_per_tok``,
    ``moe_intermediate_size``, ``shared_expert_intermediate_size`` (absent:
    no shared expert), ``norm_topk_prob``, ``moe_routed_scaling_factor``.
    Two families of keys say what each layer is. Per-layer lists:
    ``layer_types`` (``full_attention`` | ``sliding_attention``; as many
    layers are built as it lists), ``num_attention_heads_per_layer``,
    ``mlp_layer_types`` (``dense`` | ``sparse``), with ``sliding_window``,
    ``rope_parameters`` and ``gating``. Or scalars, every layer of one kind:
    ``num_hidden_layers``, ``num_attention_heads``, ``rope_theta`` /
    ``rope_scaling``, ``mlp_only_layers`` + ``decoder_sparse_step`` for
    which layers are sparse, ``sa_config`` (``indexer_num_heads``,
    ``indexer_head_dim``, ``topk``: attention over a learned selection of
    keys) and ``qk_norm`` (an RMSNorm on each head of q and k).
    A third family, a hybrid stack of one mixer a layer:
    ``hybrid_override_pattern``, a character a layer (``M`` a Mamba-2
    mixer from ``mamba_num_heads``, ``mamba_head_dim``, ``n_groups``,
    ``ssm_state_size``, ``conv_kernel``, ``chunk_size``; ``E`` an expert
    layer of ``n_routed_experts`` experts of ``moe_intermediate_size`` in
    the form ``mlp_hidden_act`` names (``relu2`` | ``silu``), sigmoid
    scores, top ``num_experts_per_tok`` times ``routed_scaling_factor``,
    inside ``moe_latent_size`` where that is given, beside
    ``n_shared_experts`` shared ones of
    ``moe_shared_expert_intermediate_size``; ``*`` attention without
    positional rotation), with ``layer_norm_epsilon``.
    ``experts_held=(count, first)`` gives the expert layers a share of the
    experts (None: all): ``first`` picks the router's columns. A share of
    the heads of attention or of the Mamba mixers (whole groups) is the
    configuration's own counts."""
    name = "sparsedecoder"

    def __init__(self, config, seed: int = 123, experts_held=None, **kwargs):
        kwargs.pop("num_classes", None)
        kwargs.pop("input_shape", None)
        super().__init__(num_classes=config["vocab_size"], seed=seed,
                         input_shape=(config["vocab_size"],), **kwargs)
        self.config = config
        self.experts_held = experts_held

    def conf(self):
        from deeplearning4j_tpu.nn.layers import (
            EmbeddingSequenceLayer, RnnOutputLayer, RMSNorm)
        c = self.config
        hybrid = "hybrid_override_pattern" in c
        vocab, hidden = c["vocab_size"], c["hidden_size"]
        eps = c["layer_norm_epsilon" if hybrid else "rms_norm_eps"]
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed)
             .updater(self.updater(Adam(1e-4)))
             .weight_init("xavier")
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(vocab)))
        g.add_layer("embed", EmbeddingSequenceLayer(
            n_in=vocab, n_out=hidden, activation="identity"), "tokens")
        layers = self._hybrid_layers if hybrid else self._paired_layers
        g.add_layer("final_norm", RMSNorm(eps=eps), layers(g, "embed", eps))
        g.add_layer("head", RnnOutputLayer(
            n_out=vocab, activation="softmax", loss="mcxent",
            has_bias=False), "final_norm")
        return g.set_outputs("head").build()

    def _hybrid_layers(self, g, prev, eps):
        """``b<i>.norm -> b<i>.mixer -> b<i>.add`` for every character of
        ``hybrid_override_pattern``; returns the last node."""
        from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
        from deeplearning4j_tpu.nn.layers import (
            ExpertLayer, Mamba2Mixer, RMSNorm, RotaryGQAttention)
        c = self.config
        pattern = c["hybrid_override_pattern"]
        if set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r}: a layer is M (Mamba-2), "
                "E (experts) or * (attention)")
        forms = {"relu2": "relu2", "silu": "swiglu"}
        for i, kind in enumerate(pattern):
            b = f"b{i}"
            g.add_layer(f"{b}.norm", RMSNorm(eps=eps), prev)
            if kind == "M":
                mixer = Mamba2Mixer(
                    n_heads=c["mamba_num_heads"], head_dim=c["mamba_head_dim"],
                    n_groups=c["n_groups"], state_size=c["ssm_state_size"],
                    conv_kernel=c["conv_kernel"], chunk_size=c["chunk_size"],
                    norm_eps=eps)
            elif kind == "E":
                mixer = ExpertLayer(
                    n_experts=c["n_routed_experts"],
                    experts_per_token=c["num_experts_per_tok"],
                    expert_width=c["moe_intermediate_size"],
                    shared_width=(c.get("moe_shared_expert_intermediate_size", 0)
                                  * c.get("n_shared_experts", 0)),
                    routed_scale=c.get("routed_scaling_factor", 1.0),
                    norm_topk=c.get("norm_topk_prob", True),
                    experts_held=self.experts_held,
                    expert_form=forms[c.get("mlp_hidden_act", "relu2")],
                    score="sigmoid",
                    latent_width=c.get("moe_latent_size") or 0)
            else:
                mixer = RotaryGQAttention(
                    n_heads=c["num_attention_heads"],
                    n_kv_heads=c["num_key_value_heads"],
                    head_dim=c["head_dim"], rotary=None)
            g.add_layer(f"{b}.mixer", mixer, f"{b}.norm")
            g.add_vertex(f"{b}.add", ElementWiseVertex(op="add"),
                         f"{b}.mixer", prev)
            prev = f"{b}.add"
        return prev

    def _paired_layers(self, g, prev, eps):
        """An attention + MLP pair a layer; returns the last node."""
        from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
        from deeplearning4j_tpu.nn.layers import (
            RMSNorm, SwiGLU, RotaryGQAttention, ExpertLayer)
        c = self.config
        kinds = c.get("layer_types") \
            or ["full_attention"] * c["num_hidden_layers"]
        heads = c.get("num_attention_heads_per_layer") \
            or [c["num_attention_heads"]] * len(kinds)
        step, dense = c.get("decoder_sparse_step", 1), c.get("mlp_only_layers", ())
        mlps = c.get("mlp_layer_types") or [
            "dense" if i in dense or (i + 1) % step else "sparse"
            for i in range(len(kinds))]
        sa = c.get("sa_config")
        extra = {"qk_norm": True, "norm_eps": eps} if c.get("qk_norm") else {}
        if sa:
            extra["indexer"] = {"heads": sa["indexer_num_heads"],
                                "head_dim": sa["indexer_head_dim"],
                                "top_k": sa["topk"]}
        for i, kind in enumerate(kinds):
            b = f"b{i}"
            g.add_layer(f"{b}.norm1", RMSNorm(eps=eps), prev)
            g.add_layer(f"{b}.attn", RotaryGQAttention(
                n_heads=heads[i],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                window=(c["sliding_window"] if kind == "sliding_attention"
                        else None),
                rotary=rotary_settings(c, kind),
                head_gate=c.get("gating") == "per-head", **extra),
                f"{b}.norm1")
            g.add_vertex(f"{b}.add1", ElementWiseVertex(op="add"),
                         f"{b}.attn", prev)
            g.add_layer(f"{b}.norm2", RMSNorm(eps=eps), f"{b}.add1")
            if mlps[i] == "dense":
                mlp = SwiGLU(width=c["intermediate_size"])
            else:
                mlp = ExpertLayer(
                    n_experts=c["num_experts"],
                    experts_per_token=c["num_experts_per_tok"],
                    expert_width=c["moe_intermediate_size"],
                    shared_width=c.get("shared_expert_intermediate_size") or 0,
                    routed_scale=c.get("moe_routed_scaling_factor", 1.0),
                    norm_topk=c.get("norm_topk_prob", True),
                    experts_held=self.experts_held)
            g.add_layer(f"{b}.mlp", mlp, f"{b}.norm2")
            g.add_vertex(f"{b}.add2", ElementWiseVertex(op="add"),
                         f"{b}.mlp", f"{b}.add1")
            prev = f"{b}.add2"
        return prev
