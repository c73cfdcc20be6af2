"""A pre-norm sparse decoder language model built from a published
configuration's own keys.

No counterpart in the reference zoo (which tops out at recurrent text
models). The graph is ``EmbeddingSequenceLayer`` in, the layers, a final
``RMSNorm`` and an untied ``RnnOutputLayer`` that takes integer labels. A
layer is an attention + MLP pair, ``RMSNorm -> RotaryGQAttention -> add ->
RMSNorm -> SwiGLU | ExpertLayer -> add``, or, in a hybrid stack, one mixer
alone, ``RMSNorm -> Mamba2Mixer | ExpertLayer | RotaryGQAttention -> add``,
or a latent-attention + MLP pair, over hyper-connected residual streams
or on the plain residual path (``SparseDecoder._latent_stack``). Nodes of
layer i are named ``b<i>.<node>``, so that ``remat='blocks'`` replays one
layer at a time.
"""

from __future__ import annotations

import math

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.updaters import Adam
from deeplearning4j_tpu.zoo.zoo_model import ZooModel


#: what ``remat='blocks'`` keeps of a latent-attention layer on the plain
#: residual path (``util/remat.py:BLOCK_KEPT`` names): the attention's output
#: and log-sum-exp, the routing and the routed experts' products. Its q, k,
#: v and the SwiGLUs' gate and up products are made again in the replay:
#: the family trains at long context, where they are the bulk of a layer
#: (512 MB and 1.37 GB in the dense layer at 32,768 positions of
#: DeepSeek-V2-Lite), for about 7 % more products a step
LATENT_PAIR_KEPT = ("attn_out", "routing", "expert_gate_up")


def _yarn_mscale(factor, mscale):
    """DeepSeek's YaRN magnitude: 0.1 mscale ln(factor) + 1 (1 unstretched)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_settings(config, layer_type):
    """``RotaryGQAttention.rotary`` from ``rope_parameters[layer_type]``,
    or from the scalar ``rope_theta`` / ``rope_scaling`` of a configuration
    whose layers are all of one kind. ``mrope_section`` (rotary frequencies
    shared out over position axes) is plain rotary while the axes are equal,
    as they are for text. The scaling's kind is ``rope_type`` or, in older
    and DeepSeek-style files, ``type``. YaRN's magnitude is
    ``attention_factor`` on cos and sin, or DeepSeek's ``mscale`` /
    ``mscale_all_dim``: cos and sin times m(mscale) / m(mscale_all_dim), the
    scores times ``score_scale`` = m(mscale_all_dim)^2. The rotated dims are
    ``qk_rope_head_dim`` where the file gives one (latent attention), else
    ``head_dim`` times ``partial_rotary_factor``."""
    if "rope_parameters" not in config:
        r = dict(config.get("rope_scaling") or {},
                 rope_theta=config["rope_theta"])
    else:
        r = config["rope_parameters"][layer_type]
    dims = config.get("qk_rope_head_dim") or int(
        config["head_dim"] * r.get("partial_rotary_factor", 1))
    out = {"theta": r["rope_theta"], "dims": dims}
    if (r.get("rope_type") or r.get("type")) == "yarn":
        out.update(
            factor=r["factor"],
            original_max_position=r["original_max_position_embeddings"],
            beta_fast=r["beta_fast"], beta_slow=r["beta_slow"])
        if "attention_factor" in r or "mscale" not in r:
            out["attention_factor"] = r["attention_factor"]
        else:
            f, all_dim = r["factor"], r.get("mscale_all_dim") or 0
            out["attention_factor"] = (_yarn_mscale(f, r["mscale"])
                                       / _yarn_mscale(f, all_dim))
            if all_dim:
                out["score_scale"] = _yarn_mscale(f, all_dim) ** 2
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
    A fourth, latent attention (``kv_lora_rank`` present):
    ``num_hidden_layers`` layers of ``LatentAttention`` (``q_lora_rank``,
    null for queries without a bottleneck, ``kv_lora_rank``,
    ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
    ``num_attention_heads``) and an MLP, ``SwiGLU`` of ``intermediate_size``
    for the first ``first_k_dense_replace`` and else an expert layer of
    ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size``
    (``scoring_func``, top ``num_experts_per_tok`` times
    ``routed_scaling_factor``, ``n_shared_experts`` shared ones of the same
    width; ``topk_method`` ``greedy`` or ``noaux_tc`` with ``n_group`` 1;
    with ``seq_aux`` the sequence-wise balance loss at ``aux_loss_alpha``).
    With ``hc_mult``, each sublayer is wrapped in a hyper-connection over
    that many residual streams (``hc_sinkhorn_iters``, ``hc_eps``,
    ``mhc_h_res_clamp_min`` / ``_max``), and with
    ``num_nextn_predict_layers`` 1 one multi-token-prediction module (an
    expert layer of the same kind over the trunk's last state joined to
    the next token's embedding) follows, whose cross entropy on the token
    after next enters the loss at ``mtp_loss_weight`` (absent: 0.3)
    through the one head both use. Without ``hc_mult`` a layer is the
    pre-norm pair on the plain residual path.
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
        latent = "kv_lora_rank" in c
        if latent and c.get("hc_mult"):
            return self._latent_stack(g, eps).set_outputs("head").build()
        layers = (self._hybrid_layers if hybrid else self._latent_pairs
                  if latent else self._paired_layers)
        g.add_layer("final_norm", RMSNorm(eps=eps), layers(g, "embed", eps))
        g.add_layer("head", RnnOutputLayer(
            n_out=vocab, activation="softmax", loss="mcxent",
            has_bias=False), "final_norm")
        conf = g.set_outputs("head").build()
        if latent:
            conf.global_conf.remat_keep = LATENT_PAIR_KEPT
        return conf

    def _latent_stack(self, g, eps):
        """The trunk of latent-attention layers over ``hc_mult``
        hyper-connected residual streams (``hc_expand`` to
        ``hc_collapse``), ``final_norm`` and ``head``, and the
        multi-token-prediction module (nodes ``mtp.*``, one replay unit)
        where the file has one."""
        from deeplearning4j_tpu.nn.conf.graph_conf import (
            MergeVertex, NextTokenJoin, StreamCollapse, StreamExpand)
        from deeplearning4j_tpu.nn.layers import (
            MultiTokenHead, NextTokenProjection, RMSNorm, RnnOutputLayer)
        c = self.config
        n, depth = c["hc_mult"], c.get("num_nextn_predict_layers", 0)
        if depth not in (0, 1):
            raise ValueError(
                f"num_nextn_predict_layers={depth!r} in the file: one "
                "multi-token-prediction module is built, or none")
        g.add_vertex("hc_expand", StreamExpand(streams=n), "embed")
        prev = "hc_expand"
        for i in range(c["num_hidden_layers"]):
            prev = self._latent_layer(g, f"b{i}", prev, eps,
                                      dense=i < c["first_k_dense_replace"])
        g.add_vertex("hc_collapse", StreamCollapse(streams=n), prev)
        g.add_layer("final_norm", RMSNorm(eps=eps), "hc_collapse")
        if not depth:
            g.add_layer("head", RnnOutputLayer(
                n_out=c["vocab_size"], activation="softmax", loss="mcxent",
                has_bias=False), "final_norm")
            return g
        g.add_vertex("mtp.join", NextTokenJoin(), "hc_collapse", "embed")
        g.add_layer("mtp.proj", NextTokenProjection(norm_eps=eps), "mtp.join")
        g.add_vertex("mtp.expand", StreamExpand(streams=n), "mtp.proj")
        last = self._latent_layer(g, "mtp", "mtp.expand", eps, dense=False)
        g.add_vertex("mtp.collapse", StreamCollapse(streams=n), last)
        g.add_layer("mtp.final_norm", RMSNorm(eps=eps), "mtp.collapse")
        g.add_vertex("head_in", MergeVertex(), "final_norm", "mtp.final_norm")
        g.add_layer("head", MultiTokenHead(
            n_out=c["vocab_size"], activation="softmax", loss="mcxent",
            has_bias=False, mtp_weight=c.get("mtp_loss_weight", 0.3)),
            "head_in")
        return g

    def _latent_pairs(self, g, prev, eps):
        """Latent-attention layers on the plain residual path, each the
        pre-norm pair of ``_pair``; returns the last node."""
        c = self.config
        depth = c.get("num_nextn_predict_layers", 0)
        if depth:
            raise ValueError(
                f"num_nextn_predict_layers={depth!r} in the file without "
                "hc_mult: the multi-token-prediction module is built over "
                "hyper-connected residual streams only")
        for i in range(c["num_hidden_layers"]):
            prev = self._pair(g, f"b{i}", prev, eps, *self._latent_mixers(
                eps, dense=i < c["first_k_dense_replace"]))
        return prev

    def _latent_mixers(self, eps, dense):
        """(the ``LatentAttention``, the MLP) of one latent-attention layer:
        ``SwiGLU`` where ``dense``, else the expert layer. Refuses a routing
        the expert layer does not implement rather than ignore its keys."""
        from deeplearning4j_tpu.nn.layers import (
            ExpertLayer, LatentAttention, SwiGLU)
        c = self.config
        attn = LatentAttention(
            n_heads=c["num_attention_heads"], q_rank=c["q_lora_rank"] or 0,
            kv_rank=c["kv_lora_rank"], nope_dim=c["qk_nope_head_dim"],
            rope_dim=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            rotary=rotary_settings(c, "full_attention"), norm_eps=eps)
        if dense:
            return attn, SwiGLU(width=c["intermediate_size"])
        method = c.get("topk_method", "greedy")
        if method not in ("greedy", "noaux_tc"):
            raise ValueError(
                f"topk_method={method!r}: the expert layer chooses its top "
                "k over all experts (greedy, or noaux_tc with a selection "
                "bias)")
        if (c.get("n_group") or 1) > 1 or (c.get("topk_group") or 1) > 1:
            raise ValueError(
                f"n_group={c.get('n_group')!r}, topk_group="
                f"{c.get('topk_group')!r}: device-limited routing (the top k "
                "inside the best groups of experts) is not built")
        return attn, ExpertLayer(
            n_experts=c["n_routed_experts"],
            experts_per_token=c["num_experts_per_tok"],
            expert_width=c["moe_intermediate_size"],
            shared_width=(c["moe_intermediate_size"]
                          * c.get("n_shared_experts", 0)),
            routed_scale=c.get("routed_scaling_factor", 1.0),
            norm_topk=c.get("norm_topk_prob", True),
            experts_held=self.experts_held,
            score=c.get("scoring_func", "softmax"),
            balance_alpha=c["aux_loss_alpha"] if c.get("seq_aux") else 0.0)

    def _latent_layer(self, g, b, prev, eps, dense):
        """``b.attn`` then ``b.mlp``, each as ``b.hc<j> -> b.in<j> ->
        b.norm<j> -> mixer -> b.out<j>`` on the residual state ``prev``;
        returns the last node."""
        from deeplearning4j_tpu.nn.conf.graph_conf import HyperIn, HyperOut
        from deeplearning4j_tpu.nn.layers import HyperConnection, RMSNorm
        c = self.config
        n = c["hc_mult"]
        attn, mlp = self._latent_mixers(eps, dense)
        for j, (name, mixer) in enumerate((("attn", attn), ("mlp", mlp)), 1):
            g.add_layer(f"{b}.hc{j}", HyperConnection(
                streams=n, sinkhorn_iters=c["hc_sinkhorn_iters"],
                eps=c["hc_eps"], clamp=(c["mhc_h_res_clamp_min"],
                                        c["mhc_h_res_clamp_max"]),
                norm_eps=eps), prev)
            g.add_vertex(f"{b}.in{j}", HyperIn(streams=n), f"{b}.hc{j}", prev)
            g.add_layer(f"{b}.norm{j}", RMSNorm(eps=eps), f"{b}.in{j}")
            g.add_layer(f"{b}.{name}", mixer, f"{b}.norm{j}")
            g.add_vertex(f"{b}.out{j}", HyperOut(streams=n), f"{b}.{name}",
                         f"{b}.hc{j}", prev)
            prev = f"{b}.out{j}"
        return prev

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
        from deeplearning4j_tpu.nn.layers import (
            SwiGLU, RotaryGQAttention, ExpertLayer)
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
            attn = RotaryGQAttention(
                n_heads=heads[i],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                window=(c["sliding_window"] if kind == "sliding_attention"
                        else None),
                rotary=rotary_settings(c, kind),
                head_gate=c.get("gating") == "per-head", **extra)
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
            prev = self._pair(g, f"b{i}", prev, eps, attn, mlp)
        return prev

    @staticmethod
    def _pair(g, b, prev, eps, attn, mlp):
        """``b.norm1 -> b.attn -> b.add1 -> b.norm2 -> b.mlp -> b.add2``:
        the pre-norm pair on the plain residual path from ``prev``; returns
        ``b.add2``."""
        from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
        from deeplearning4j_tpu.nn.layers import RMSNorm
        for j, (name, mixer, add_to) in enumerate(
                (("attn", attn, prev), ("mlp", mlp, f"{b}.add1")), 1):
            g.add_layer(f"{b}.norm{j}", RMSNorm(eps=eps), add_to)
            g.add_layer(f"{b}.{name}", mixer, f"{b}.norm{j}")
            g.add_vertex(f"{b}.add{j}", ElementWiseVertex(op="add"),
                         f"{b}.{name}", add_to)
        return f"{b}.add2"
