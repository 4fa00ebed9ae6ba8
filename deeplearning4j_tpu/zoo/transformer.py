"""Transformer zoo models — modern extension beyond the reference zoo.

The reference zoo's sequence model is TextGenerationLSTM
(`zoo/model/TextGenerationLSTM.java`); these are its transformer-class
successors, required by the project charter's long-context mandate
(SURVEY §7 step 7). Built entirely from the framework's own layers:
EmbeddingSequenceLayer + PositionEmbeddingLayer + TransformerEncoderBlock
(flash attention on TPU inference; MoE experts optional; ring attention
under a `seq`-axis mesh via parallel.ring_attention).
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.attention import (
    LatentAttention, LinearAttention, MultiHeadAttention,
    PositionEmbeddingLayer, PreNormBlock, PreNormSublayer,
    SandwichTransformerBlock, SelectiveStateSpace, TransformerEncoderBlock,
)
from deeplearning4j_tpu.nn.layers.feedforward import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.layers.normalization import RMSNormalization
from deeplearning4j_tpu.nn.layers.recurrent import (
    ExitGatedOutputLayer, MultiTokenOutputLayer, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.layers.special import LoopedStack
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.zoo.base import ZooModel, register_zoo


@register_zoo
class TextGenerationTransformer(ZooModel):
    """GPT-style causal byte/char LM.

    Inputs: token ids as [batch, time, 1]; outputs per-timestep softmax
    over the vocabulary (same contract as TextGenerationLSTM, so the
    text-generation tooling is interchangeable).
    """

    num_classes = 256             # byte vocabulary
    input_shape = (256, 1)        # (timesteps, 1 token-id channel)

    def __init__(self, *args, d_model: int = 256, num_heads: int = 8,
                 num_kv_heads=None, num_blocks: int = 4, n_experts: int = 0,
                 pos_encoding: str = "learned", max_decode: int = 0,
                 norm: str = "layer", ffn_activation: str = "gelu",
                 window=None, rolling_cache: bool = False, **kw):
        super().__init__(*args, **kw)
        self.d_model = d_model
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads   # < num_heads -> GQA
        self.num_blocks = num_blocks
        self.n_experts = n_experts
        # norm="rms" + ffn_activation="swiglu" + pos_encoding="rope" +
        # num_kv_heads < num_heads = the Llama-architecture block shape
        self.norm = norm
        self.ffn_activation = ffn_activation
        # window: int applies to every block; a list/tuple gives each
        # block its own (None = full attention) — the alternating
        # local/global pattern (Gemma-style) is window=[w, None]*k
        self.window = window
        if isinstance(window, (list, tuple)):
            if len(window) != num_blocks:
                raise ValueError(
                    f"per-block window list has {len(window)} entries "
                    f"for {num_blocks} blocks")
            if rolling_cache and any(w is None for w in window):
                raise ValueError(
                    "rolling_cache needs a window on EVERY block (a "
                    "full-attention block's cache cannot roll)")
        if rolling_cache and (window is None or pos_encoding != "rope"):
            raise ValueError(
                "rolling_cache streams unbounded generation in O(window) "
                "memory: it needs window=w and pos_encoding='rope' "
                "(learned positions cap decode length anyway)")
        if rolling_cache and max_decode:
            raise ValueError(
                "rolling_cache makes generation length unbounded — "
                "max_decode would be silently ignored; drop one of them")
        self.rolling_cache = rolling_cache
        if pos_encoding not in ("learned", "rope"):
            raise ValueError(f"pos_encoding must be 'learned' or 'rope', "
                             f"got {pos_encoding!r}")
        if max_decode and pos_encoding != "rope":
            raise ValueError(
                "max_decode extends generation past the training length, "
                "which needs pos_encoding='rope' (learned positions are "
                "hard-capped at the table size)")
        self.pos_encoding = pos_encoding
        self.max_decode = max_decode   # rope only: decode budget beyond t

    def conf(self):
        t = self.input_shape[0]
        vocab = self.num_classes
        rope = self.pos_encoding == "rope"
        # learned positions cap decode length at t, so a bigger KV cache
        # would be unreachable; RoPE has no absolute-position table, so
        # the cache (and thus generation) may extend past the training t.
        # A rolling cache needs only prefill + window slots — generation
        # length is unbounded in that fixed buffer.
        per_block = (list(self.window)
                     if isinstance(self.window, (list, tuple))
                     else [self.window] * self.num_blocks)

        def block_cache(w):
            if self.rolling_cache:
                return t + w - 1     # prefill + window ring slots
            return max(t, self.max_decode) if rope else t

        blocks = [
            TransformerEncoderBlock(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                causal=True, n_experts=self.n_experts,
                max_cache=block_cache(w), rope=rope, norm=self.norm,
                ffn_activation=self.ffn_activation, window=w,
                rolling_cache=self.rolling_cache)
            for w in per_block
        ]
        pos = [] if rope else [PositionEmbeddingLayer(max_length=t)]
        return (NeuralNetConfiguration.builder()
                .seed(self.seed)
                .updater(self.kw.get("updater", Adam(3e-4)))
                .activation("identity")
                .weight_init("xavier")
                .list(
                    EmbeddingSequenceLayer(n_in=vocab, n_out=self.d_model,
                                           activation="identity"),
                    *pos,
                    *blocks,
                    RnnOutputLayer(n_out=vocab, activation="softmax",
                                   loss="mcxent"))
                .set_input_type(InputType.recurrent(1, t))
                .build())


@register_zoo
class SparseSandwichTransformer(ZooModel):
    """A causal language model of the `afmoe` family (Arcee Trinity),
    built from the keys its published `config.json` has: sandwich-norm
    blocks (`SandwichTransformerBlock`) whose attention is gated, GQA,
    normed per head, over a sliding window with rotary positions where
    `layer_types` says "sliding_attention" and over the whole causal
    context with no positions at all where it says "full_attention"; the
    first `num_dense_layers` blocks have a dense SwiGLU of
    `intermediate_size`, the rest sigmoid-routed experts
    (`num_experts`, `num_experts_per_tok`, `num_shared_experts`,
    `moe_intermediate_size`, `route_norm`, `route_scale`) with a selection
    bias. Embedding rows are scaled by sqrt(hidden_size) where
    `mup_enabled`; the head is its own matrix behind a last RMS norm.

    One device's share of a deployment is built with `experts_held`
    (first, count): the experts of every expert layer whose kernels live
    here, and `vocabulary_held`: the rows of the embedding and the head
    that do. Token ids come as `[batch, time]` integers, labels as
    integers (`sparse_mcxent`)."""

    input_shape = (8192,)

    def __init__(self, config: dict, *, timesteps: int = None,
                 experts_held=None, vocabulary_held: int = None,
                 dtype: str = "float32", gradient_checkpointing=False, **kw):
        super().__init__(
            num_classes=vocabulary_held or config["vocab_size"],
            input_shape=(timesteps or self.input_shape[0],), **kw)
        kinds = list(config["layer_types"])
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError(
                f"layer_types has {len(kinds)} entries for "
                f"{config['num_hidden_layers']} layers")
        unknown = set(kinds) - {"sliding_attention", "full_attention"}
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)} not known")
        self.config = dict(config)
        self.experts_held = experts_held
        self.dtype = dtype
        self.gradient_checkpointing = gradient_checkpointing

    def conf(self):
        c, t = self.config, self.input_shape[0]
        d = c["hidden_size"]
        blocks = []
        for i, kind in enumerate(c["layer_types"]):
            sliding = kind == "sliding_attention"
            sparse = i >= c["num_dense_layers"]
            blocks.append(SandwichTransformerBlock(
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"],
                head_dim=c["head_dim"], qk_norm=True, output_gate=True,
                causal=True, rope=sliding,
                rope_base=float(c.get("rope_theta", 10000)),
                window=c["sliding_window"] if sliding else None,
                max_cache=t, eps=c["rms_norm_eps"],
                ffn_width=c["intermediate_size"],
                n_experts=c["num_experts"] if sparse else 0,
                experts_held=self.experts_held,
                moe_k=c["num_experts_per_tok"],
                expert_width=c["moe_intermediate_size"],
                n_shared=c["num_shared_experts"],
                score=c.get("score_func", "sigmoid"), selection_bias=True,
                route_norm=c["route_norm"], route_scale=c["route_scale"]))
        builder = (NeuralNetConfiguration.builder()
                   .seed(self.seed)
                   .updater(self.kw.get("updater", Adam(3e-4)))
                   .activation("identity")
                   .weight_init("xavier")
                   .dtype(self.dtype))
        if self.gradient_checkpointing:
            builder = builder.gradient_checkpointing()
        return (builder.list(
            EmbeddingSequenceLayer(
                n_in=self.num_classes, n_out=d, activation="identity",
                scale=d ** 0.5 if c.get("mup_enabled") else None),
            *blocks,
            RMSNormalization(eps=c["rms_norm_eps"]),
            RnnOutputLayer(n_out=self.num_classes, has_bias=False,
                           activation="softmax", loss="sparse_mcxent"))
            .set_input_type(InputType.recurrent(1, t))
            .build())


@register_zoo
class LoopedSandwichTransformer(ZooModel):
    """A looped causal language model of the `ouro` family (ByteDance
    Ouro), built from the keys its published `config.json` has:
    `num_hidden_layers` sandwich-norm blocks (`SandwichTransformerBlock`:
    `num_attention_heads` heads of `head_dim` with `num_key_value_heads`,
    rotary positions at `rope_theta`, a SwiGLU of `intermediate_size`, RMS
    norms at `rms_norm_eps`, no bias) whose whole stack runs
    `total_ut_steps` times over the same weights (`LoopedStack`), the last
    norm after every pass and its output the next pass's input; a head of
    its own scores every pass and a learned gate weighs them
    (`ExitGatedOutputLayer`, `exit_entropy_beta` 0.1 where the config
    gives none). Inference runs all the passes and reads the last.

    `layer_types` other than "full_attention", a tied head, and an
    activation other than silu are errors. Token ids come as
    `[batch, time]` integers, labels as integers (`sparse_mcxent`)."""

    input_shape = (8192,)

    def __init__(self, config: dict, *, timesteps: int = None,
                 dtype: str = "float32", gradient_checkpointing=False, **kw):
        super().__init__(num_classes=config["vocab_size"],
                         input_shape=(timesteps or self.input_shape[0],),
                         **kw)
        kinds = list(config.get("layer_types")
                     or ["full_attention"] * config["num_hidden_layers"])
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError(
                f"layer_types has {len(kinds)} entries for "
                f"{config['num_hidden_layers']} layers")
        unknown = set(kinds) - {"full_attention"}
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)} not known: a "
                             f"looped stack here is full attention")
        if config.get("tie_word_embeddings", False):
            raise ValueError("a tied head is not wired for a looped model")
        if config.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {config['hidden_act']!r}: the "
                             f"block's feed-forward is a SwiGLU")
        self.config = dict(config)
        self.dtype = dtype
        self.gradient_checkpointing = gradient_checkpointing

    def conf(self):
        c, t = self.config, self.input_shape[0]
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        block = SandwichTransformerBlock(
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or d // c["num_attention_heads"],
            causal=True, rope=True, rope_base=float(c["rope_theta"]),
            max_cache=t, eps=eps, ffn_width=c["intermediate_size"])
        builder = (NeuralNetConfiguration.builder()
                   .seed(self.seed)
                   .updater(self.kw.get("updater", Adam(3e-4)))
                   .activation("identity")
                   .weight_init("xavier")
                   .dtype(self.dtype))
        if self.gradient_checkpointing:
            builder = builder.gradient_checkpointing()
        passes = c["total_ut_steps"]
        return (builder.list(
            EmbeddingSequenceLayer(n_in=self.num_classes, n_out=d,
                                   activation="identity"),
            LoopedStack(layers=(block,) * c["num_hidden_layers"],
                        passes=passes, norm=RMSNormalization(eps=eps)),
            ExitGatedOutputLayer(n_out=self.num_classes, passes=passes,
                                 beta=c.get("exit_entropy_beta", 0.1),
                                 activation="softmax",
                                 loss="sparse_mcxent"))
            .set_input_type(InputType.recurrent(1, t))
            .build())


@register_zoo
class HybridLinearSparseTransformer(ZooModel):
    """A causal language model of the `minicpm_sala` family (MiniCPM-SALA),
    built from the keys its published `config.json` has: pre-norm blocks
    (`PreNormBlock`) whose halves enter the residual stream times
    `scale_depth / sqrt(mup_denominator)`, with a SwiGLU of
    `intermediate_size` and, by `mixer_types`, one of two mixers.
    "lightning-attn": decayed linear attention (`LinearAttention`:
    `lightning_nh` heads of `lightning_head_dim`, q and k normed per head,
    rotary positions where `lightning_use_rope`, an output norm and gate).
    "minicpm4": gated GQA softmax attention with no positions
    (`attn_use_rope` false) that, past `sparse_config.dense_len` tokens,
    reads `topk` blocks of keys a query and KV group (InfLLM-V2;
    `sparse_config` as MiniCPM4 publishes it, whose sizes are
    `ops.sparse_attention.BlockSelection`'s defaults where the config has
    none). Embedding rows are scaled by `scale_emb`;
    the head is its own matrix behind a last RMS norm whose rows are
    divided by `hidden_size / dim_model_base`.

    The first pipeline stage's share of a deployment is built with
    `layers_published`: `mixer_types` then lists this stage's layers
    only, and a linear layer's decay follows its index in the whole
    model. `vocabulary_held`: the rows of the embedding and the
    head that live here. Token ids come as `[batch, time]` integers,
    labels as integers (`sparse_mcxent`)."""

    input_shape = (16384,)

    def __init__(self, config: dict, *, timesteps: int = None,
                 vocabulary_held: int = None, layers_published: int = None,
                 dtype: str = "float32", gradient_checkpointing=False, **kw):
        super().__init__(
            num_classes=vocabulary_held or config["vocab_size"],
            input_shape=(timesteps or self.input_shape[0],), **kw)
        kinds = list(config["mixer_types"])
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError(
                f"mixer_types has {len(kinds)} entries for "
                f"{config['num_hidden_layers']} layers")
        unknown = set(kinds) - {"minicpm4", "lightning-attn"}
        if unknown:
            raise ValueError(f"mixer_types {sorted(unknown)} not known")
        if config.get("rope_theta", 10000) != 10000:
            raise ValueError("rope_theta other than 10000 is not wired")
        self.config = dict(config)
        self.layers_published = layers_published or len(kinds)
        self.dtype = dtype
        self.gradient_checkpointing = gradient_checkpointing

    def _mixer(self, kind: str, index: int):
        from deeplearning4j_tpu.ops.sparse_attention import BlockSelection

        c, t = self.config, self.input_shape[0]
        eps = c["rms_norm_eps"]
        if kind == "lightning-attn":
            return LinearAttention(
                num_heads=c["lightning_nh"], num_kv_heads=c["lightning_nkv"],
                head_dim=c["lightning_head_dim"], qk_norm=c["qk_norm"],
                norm_eps=eps, rope=c["lightning_use_rope"],
                output_gate=c["use_output_gate"],
                output_norm=c["use_output_norm"],
                decay_layer=index,
                decay_layers=self.layers_published)
        sizes = {k: v for k, v in c.get("sparse_config", {}).items()
                 if k in BlockSelection._fields}
        return MultiHeadAttention(
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
            qk_norm=c["qk_norm"], norm_eps=eps, rope=c["attn_use_rope"],
            output_gate=c["attn_use_output_gate"],
            bias=c.get("attention_bias", False), causal=True, max_cache=t,
            sparse=BlockSelection(**sizes))

    def conf(self):
        c, t = self.config, self.input_shape[0]
        d = c["hidden_size"]
        blocks = [
            PreNormBlock(mixer=self._mixer(kind, i),
                         ffn_width=c["intermediate_size"],
                         residual_scale=(c["scale_depth"]
                                         / c["mup_denominator"] ** 0.5),
                         eps=c["rms_norm_eps"])
            for i, kind in enumerate(c["mixer_types"])]
        builder = (NeuralNetConfiguration.builder()
                   .seed(self.seed)
                   .updater(self.kw.get("updater", Adam(3e-4)))
                   .activation("identity")
                   .weight_init("xavier")
                   .dtype(self.dtype))
        if self.gradient_checkpointing:
            builder = builder.gradient_checkpointing()
        return (builder.list(
            EmbeddingSequenceLayer(n_in=self.num_classes, n_out=d,
                                   activation="identity",
                                   scale=c["scale_emb"]),
            *blocks,
            RMSNormalization(eps=c["rms_norm_eps"],
                             scale=c["dim_model_base"] / d),
            RnnOutputLayer(n_out=self.num_classes, has_bias=False,
                           activation="softmax", loss="sparse_mcxent"))
            .set_input_type(InputType.recurrent(1, t))
            .build())


@register_zoo
class LatentSparseTransformer(ZooModel):
    """A causal language model of the `deepseek_v2` family, built from the
    keys its published `config.json` has: pre-norm blocks (`PreNormBlock`)
    whose mixer is multi-head latent attention (`LatentAttention`:
    `q_lora_rank`, `kv_lora_rank`, `qk_nope_head_dim`, `qk_rope_head_dim`,
    `v_head_dim`, rotary positions at `rope_theta` under `rope_scaling`,
    type "yarn") and whose other half is a dense SwiGLU of
    `intermediate_size` in the first `first_k_dense_replace` blocks and,
    after them, softmax-routed experts (`parallel/moe.ExpertFeedForward`:
    `n_routed_experts` of `moe_intermediate_size`, `num_experts_per_tok` a
    token chosen inside the `topk_group` best of `n_group` groups,
    weights not normalised unless `norm_topk_prob`, times
    `routed_scaling_factor`, beside `n_shared_experts` shared ones). The
    head is its own matrix behind a last RMS norm. The balance losses and
    the paper's token dropping are not built.

    One device's share of a deployment is built with `heads_held` (first,
    count): the attention heads of every layer whose slices live here,
    `experts_held` (first, count): the routed experts of every expert
    layer whose kernels do, and `vocabulary_held`: the rows of the
    embedding and the head that do. Token ids come as `[batch, time]`
    integers, labels as integers (`sparse_mcxent`)."""

    input_shape = (8192,)

    def __init__(self, config: dict, *, timesteps: int = None,
                 heads_held=None, experts_held=None,
                 vocabulary_held: int = None, dtype: str = "float32",
                 gradient_checkpointing=False, **kw):
        super().__init__(
            num_classes=vocabulary_held or config["vocab_size"],
            input_shape=(timesteps or self.input_shape[0],), **kw)
        for key, known in (("topk_method", ("group_limited_greedy",
                                            "greedy")),
                           ("scoring_func", ("softmax",))):
            if config[key] not in known:
                raise ValueError(f"{key} {config[key]!r} is not known "
                                 f"({', '.join(known)} are)")
        scaling = config.get("rope_scaling")
        if scaling is not None and scaling.get("type") != "yarn":
            raise ValueError(f"rope_scaling type {scaling.get('type')!r} is "
                             f"not known (yarn is)")
        if config.get("moe_layer_freq", 1) != 1:
            raise ValueError("moe_layer_freq other than 1 is not wired")
        self.config = dict(config)
        self.heads_held = heads_held
        self.experts_held = experts_held
        self.dtype = dtype
        self.gradient_checkpointing = gradient_checkpointing

    def conf(self):
        from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

        c, t = self.config, self.input_shape[0]
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        mixer = LatentAttention(
            num_heads=c["num_attention_heads"], heads_held=self.heads_held,
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"], rope_theta=float(c["rope_theta"]),
            rope_scaling=c.get("rope_scaling"), norm_eps=eps)
        grouped = c["topk_method"] == "group_limited_greedy"
        experts = ExpertFeedForward(
            width=c["moe_intermediate_size"], n_experts=c["n_routed_experts"],
            held=None if self.experts_held is None
            else tuple(self.experts_held),
            k=c["num_experts_per_tok"], score=c["scoring_func"],
            route_norm=c["norm_topk_prob"],
            route_scale=c["routed_scaling_factor"],
            n_shared=c["n_shared_experts"],
            n_group=c["n_group"] if grouped else 1,
            topk_group=c["topk_group"] if grouped else 1)
        blocks = [
            PreNormBlock(mixer=mixer, ffn_width=c["intermediate_size"],
                         ffn=None if i < c["first_k_dense_replace"]
                         else experts, eps=eps)
            for i in range(c["num_hidden_layers"])]
        builder = (NeuralNetConfiguration.builder()
                   .seed(self.seed)
                   .updater(self.kw.get("updater", Adam(3e-4)))
                   .activation("identity")
                   .weight_init("xavier")
                   .dtype(self.dtype))
        if self.gradient_checkpointing:
            builder = builder.gradient_checkpointing()
        return (builder.list(
            EmbeddingSequenceLayer(n_in=self.num_classes, n_out=d,
                                   activation="identity"),
            *blocks,
            RMSNormalization(eps=eps),
            RnnOutputLayer(n_out=self.num_classes, has_bias=False,
                           activation="softmax", loss="sparse_mcxent"))
            .set_input_type(InputType.recurrent(1, t))
            .build())


@register_zoo
class HybridStateSpaceTransformer(ZooModel):
    """A causal language model of the `granitemoehybrid` family (Granite
    4.0-H), built from the keys its published `config.json` has: pre-norm
    blocks (`PreNormBlock`) whose halves enter the residual stream times
    `residual_multiplier`, with, by `layer_types`, one of two mixers.
    "mamba": a Mamba-2 selective state-space mixer (`SelectiveStateSpace`:
    `mamba_n_heads` heads of `mamba_d_head`, `mamba_d_state`,
    `mamba_n_groups`, a causal convolution of `mamba_d_conv`, chunks of
    `mamba_chunk_size`). "attention": GQA softmax attention with no
    positions (`position_embedding_type` "nope") whose softmax scale is
    `attention_multiplier`. Every block's other half is softmax-routed
    experts (`parallel/moe.ExpertFeedForward`: `num_local_experts` of
    `intermediate_size`, `num_experts_per_tok` a token, the weights a
    softmax over the chosen logits) beside a shared SwiGLU of
    `shared_intermediate_size`. Embedding rows are scaled by
    `embedding_multiplier`; the head is the embedding transposed
    (`tie_word_embeddings`) behind a last RMS norm, the logits divided by
    `logits_scaling`. No balance loss is built (the config gives no
    coefficient).

    One device's share of a deployment is built with `heads_held` (first,
    count): the Mamba heads of every `mamba` layer whose slices live here,
    `attention_heads_held` and `kv_heads_held`: the query and KV heads of
    every `attention` layer that do, `experts_held`: the routed experts of
    every block whose kernels do, and `vocabulary_held`: the rows of the
    tied embedding that do. Token ids come as `[batch, time]` integers,
    labels as integers (`sparse_mcxent`)."""

    input_shape = (8192,)

    def __init__(self, config: dict, *, timesteps: int = None,
                 heads_held=None, attention_heads_held=None,
                 kv_heads_held=None, experts_held=None,
                 vocabulary_held: int = None, dtype: str = "float32",
                 gradient_checkpointing=False, **kw):
        super().__init__(
            num_classes=vocabulary_held or config["vocab_size"],
            input_shape=(timesteps or self.input_shape[0],), **kw)
        kinds = list(config["layer_types"])
        if len(kinds) != config["num_hidden_layers"]:
            raise ValueError(
                f"layer_types has {len(kinds)} entries for "
                f"{config['num_hidden_layers']} layers")
        unknown = set(kinds) - {"mamba", "attention"}
        if unknown:
            raise ValueError(f"layer_types {sorted(unknown)} not known")
        for key, known in (("position_embedding_type", "nope"),
                           ("normalization_function", "rmsnorm"),
                           ("hidden_act", "silu"),
                           ("mamba_proj_bias", False),
                           ("mamba_conv_bias", True),
                           ("attention_bias", False),
                           ("tie_word_embeddings", True)):
            if config[key] != known:
                raise ValueError(f"{key} {config[key]!r} is not wired "
                                 f"({known!r} is)")
        if (config["mamba_expand"] * config["hidden_size"]
                != config["mamba_n_heads"] * config["mamba_d_head"]):
            raise ValueError("mamba_expand x hidden_size is not "
                             "mamba_n_heads x mamba_d_head")
        if config["shared_intermediate_size"] % config["intermediate_size"]:
            raise ValueError("the shared expert is no whole number of "
                             "experts wide")
        self.config = dict(config)
        self.heads_held = heads_held
        self.attention_heads_held = attention_heads_held
        self.kv_heads_held = kv_heads_held
        self.experts_held = experts_held
        self.dtype = dtype
        self.gradient_checkpointing = gradient_checkpointing

    def _mixer(self, kind: str):
        c, t = self.config, self.input_shape[0]
        if kind == "mamba":
            return SelectiveStateSpace(
                num_heads=c["mamba_n_heads"], heads_held=self.heads_held,
                head_dim=c["mamba_d_head"], state_size=c["mamba_d_state"],
                n_groups=c["mamba_n_groups"], conv_kernel=c["mamba_d_conv"],
                chunk=c["mamba_chunk_size"], norm_eps=c["rms_norm_eps"])
        heads = c["num_attention_heads"]
        held = (self.attention_heads_held or (0, heads))[1]
        kv_held = (self.kv_heads_held or (0, c["num_key_value_heads"]))[1]
        return MultiHeadAttention(
            num_heads=held, num_kv_heads=kv_held,
            head_dim=c["hidden_size"] // heads, causal=True, rope=False,
            bias=False, max_cache=t,
            softmax_scale=c["attention_multiplier"])

    def conf(self):
        from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

        c, t = self.config, self.input_shape[0]
        d, eps = c["hidden_size"], c["rms_norm_eps"]
        experts = ExpertFeedForward(
            width=c["intermediate_size"], n_experts=c["num_local_experts"],
            held=None if self.experts_held is None
            else tuple(self.experts_held),
            k=c["num_experts_per_tok"], score="softmax", route_norm=True,
            n_shared=c["shared_intermediate_size"] // c["intermediate_size"])
        blocks = [
            PreNormBlock(mixer=self._mixer(kind), ffn=experts,
                         residual_scale=c["residual_multiplier"], eps=eps)
            for kind in c["layer_types"]]
        builder = (NeuralNetConfiguration.builder()
                   .seed(self.seed)
                   .updater(self.kw.get("updater", Adam(3e-4)))
                   .activation("identity")
                   .weight_init("xavier")
                   .dtype(self.dtype))
        if self.gradient_checkpointing:
            builder = builder.gradient_checkpointing()
        return (builder.list(
            EmbeddingSequenceLayer(n_in=self.num_classes, n_out=d,
                                   activation="identity",
                                   scale=c["embedding_multiplier"]),
            *blocks,
            RMSNormalization(eps=eps, scale=1.0 / c["logits_scaling"]),
            RnnOutputLayer(n_out=self.num_classes, has_bias=False,
                           activation="softmax", loss="sparse_mcxent",
                           tied_to=0))
            .set_input_type(InputType.recurrent(1, t))
            .build())


@register_zoo
class HybridLatentExpertTransformer(ZooModel):
    """A causal language model of the `nemotron_h` family (Nemotron-3),
    built from the keys its published `config.json` has. Every layer is
    ONE pre-norm residual sublayer (`PreNormSublayer`), by
    `hybrid_override_pattern`: `M` a Mamba-2 mixer (`SelectiveStateSpace`:
    `mamba_num_heads` heads of `mamba_head_dim`, `ssm_state_size`,
    `n_groups` groups of B and C with the gated norm per group, a causal
    convolution of `conv_kernel`, chunks of `chunk_size`), `*` GQA softmax
    attention with no positions (`num_attention_heads` heads of `head_dim`
    over `num_key_value_heads`; the family applies no rotary embedding, so
    `rope_theta` and `partial_rotary_factor` are not read), `E` a
    LatentMoE layer (`parallel/moe.ExpertFeedForward`: a sigmoid router
    over `n_routed_experts` with a selection bias, `num_experts_per_tok` a
    token, weights normalised where `norm_topk_prob` and times
    `routed_scaling_factor`; two-matrix `mlp_hidden_act` experts of
    `moe_intermediate_size` inside a latent of `moe_latent_size`; a shared
    expert of `moe_shared_expert_intermediate_size` at the model's
    width). A last RMS norm and an untied head follow; with
    `num_nextn_predict_layers` 1 the head is a `MultiTokenOutputLayer`
    whose module's layers `mtp_hybrid_override_pattern` names and whose
    second loss term weighs `mtp_loss_scaling_factor` (0.1 where the
    config gives none). Mamba-2's initial step sizes are the config's
    `time_step_min` to `time_step_max`. No balance loss and no bias
    update is built (the config gives no coefficient).

    Refused by name: a pattern letter other than `M`, `*`, `E` (the
    family's `-`, a dense MLP layer, is not wired), a tied head, biases,
    activations other than silu (mixer) and relu2 (experts), group-limited
    routing, more prediction modules than one.

    One device's share of a deployment is built with `heads_held` (first,
    count): whole groups of the Mamba heads of every `M` layer,
    `attention_heads_held` and `kv_heads_held`: the query and KV heads of
    every `*` layer, `experts_held`: the routed experts of every `E`
    layer, and `vocabulary_held`: the rows of the embedding and the
    columns of the head. Token ids come as `[batch, time]` integers,
    labels as integers (`sparse_mcxent`)."""

    input_shape = (8192,)

    def __init__(self, config: dict, *, timesteps: int = None,
                 heads_held=None, attention_heads_held=None,
                 kv_heads_held=None, experts_held=None,
                 vocabulary_held: int = None, dtype: str = "float32",
                 gradient_checkpointing=False, **kw):
        super().__init__(
            num_classes=vocabulary_held or config["vocab_size"],
            input_shape=(timesteps or self.input_shape[0],), **kw)
        pattern = config["hybrid_override_pattern"]
        mtp = config.get("mtp_hybrid_override_pattern", "") \
            if config.get("num_nextn_predict_layers", 0) else ""
        if len(pattern) != config["num_hidden_layers"]:
            raise ValueError(
                f"hybrid_override_pattern has {len(pattern)} letters for "
                f"{config['num_hidden_layers']} layers")
        unknown = set(pattern + mtp) - set("M*E")
        if unknown:
            raise ValueError(
                f"pattern letters {sorted(unknown)} are not wired (M, * "
                f"and E are)")
        if config.get("num_nextn_predict_layers", 0) not in (0, 1):
            raise ValueError("num_nextn_predict_layers "
                             f"{config['num_nextn_predict_layers']}: one "
                             f"prediction module is wired")
        for key, known in (("mamba_hidden_act", "silu"),
                           ("mlp_hidden_act", "relu2"),
                           ("mamba_proj_bias", False),
                           ("use_conv_bias", True), ("use_bias", False),
                           ("attention_bias", False), ("mlp_bias", False),
                           ("tie_word_embeddings", False),
                           ("residual_in_fp32", False),
                           ("n_group", 1), ("topk_group", 1),
                           ("n_shared_experts", 1),
                           ("norm_eps", config["layer_norm_epsilon"])):
            if config[key] != known:
                raise ValueError(f"{key} {config[key]!r} is not wired "
                                 f"({known!r} is)")
        if (config["expand"] * config["hidden_size"]
                != config["mamba_num_heads"] * config["mamba_head_dim"]):
            raise ValueError("expand x hidden_size is not mamba_num_heads "
                             "x mamba_head_dim")
        steps = (config["time_step_min"], config["time_step_max"])
        if steps != SelectiveStateSpace.DT_RANGE \
                or config["time_step_floor"] > steps[0]:
            raise ValueError(
                f"time_step_min, time_step_max {steps} with floor "
                f"{config['time_step_floor']}: the mixer starts its steps "
                f"in {SelectiveStateSpace.DT_RANGE}, unclamped")
        self.config = dict(config)
        self.heads_held = heads_held
        self.attention_heads_held = attention_heads_held
        self.kv_heads_held = kv_heads_held
        self.experts_held = experts_held
        self.dtype = dtype
        self.gradient_checkpointing = gradient_checkpointing

    def _layer(self, letter: str):
        from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

        c, t = self.config, self.input_shape[0]
        if letter == "M":
            inner = SelectiveStateSpace(
                num_heads=c["mamba_num_heads"], heads_held=self.heads_held,
                head_dim=c["mamba_head_dim"], state_size=c["ssm_state_size"],
                n_groups=c["n_groups"], conv_kernel=c["conv_kernel"],
                chunk=c["chunk_size"], norm_eps=c["layer_norm_epsilon"])
        elif letter == "*":
            held = (self.attention_heads_held
                    or (0, c["num_attention_heads"]))[1]
            kv_held = (self.kv_heads_held
                       or (0, c["num_key_value_heads"]))[1]
            inner = MultiHeadAttention(
                num_heads=held, num_kv_heads=kv_held,
                head_dim=c["head_dim"], causal=True, rope=False, bias=False,
                max_cache=t)
        else:
            inner = ExpertFeedForward(
                width=c["moe_intermediate_size"],
                n_experts=c["n_routed_experts"],
                held=None if self.experts_held is None
                else tuple(self.experts_held),
                k=c["num_experts_per_tok"], score="sigmoid",
                selection_bias=True, route_norm=c["norm_topk_prob"],
                route_scale=float(c["routed_scaling_factor"]),
                expert_form=c["mlp_hidden_act"],
                latent=c.get("moe_latent_size"),
                shared_width=c["moe_shared_expert_intermediate_size"])
        return PreNormSublayer(layer=inner, eps=c["layer_norm_epsilon"])

    def conf(self):
        c, t = self.config, self.input_shape[0]
        d, eps = c["hidden_size"], c["layer_norm_epsilon"]
        builder = (NeuralNetConfiguration.builder()
                   .seed(self.seed)
                   .updater(self.kw.get("updater", Adam(3e-4)))
                   .activation("identity")
                   .weight_init("xavier")
                   .dtype(self.dtype))
        if self.gradient_checkpointing:
            builder = builder.gradient_checkpointing()
        if c.get("num_nextn_predict_layers", 0):
            head = (MultiTokenOutputLayer(
                n_out=self.num_classes, tied_to=0, eps=eps,
                layers=tuple(self._layer(letter) for letter in
                             c["mtp_hybrid_override_pattern"]),
                mtp_weight=c.get("mtp_loss_scaling_factor", 0.1),
                remat=bool(self.gradient_checkpointing),
                activation="softmax", loss="sparse_mcxent"),)
        else:
            head = (RMSNormalization(eps=eps),
                    RnnOutputLayer(n_out=self.num_classes, has_bias=False,
                                   activation="softmax",
                                   loss="sparse_mcxent"))
        return (builder.list(
            EmbeddingSequenceLayer(n_in=self.num_classes, n_out=d,
                                   activation="identity"),
            *(self._layer(letter)
              for letter in c["hybrid_override_pattern"]),
            *head)
            .set_input_type(InputType.recurrent(1, t))
            .build())
