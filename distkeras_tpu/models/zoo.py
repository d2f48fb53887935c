"""Model zoo — the five benchmark model families from BASELINE.json.

The reference defines its models ad hoc in notebooks (``examples/
mnist.ipynb`` builds a Keras Sequential MLP/convnet inline; the workflow
notebook reuses them).  We ship them as constructors so trainers, tests and
benchmarks share one definition:

1. ``mlp_mnist``       — SingleTrainer MLP on MNIST (the 99%-acc anchor)
2. ``convnet_cifar10`` — ADAG ConvNet on CIFAR-10
3. ``resnet20``        — DOWNPOUR ResNet-20 on CIFAR-10 (He et al. 2015,
                         the CIFAR variant: 3 stages × 3 blocks, 16/32/64)
4. ``lstm_imdb``       — AEASGD/EAMSGD LSTM sentiment on IMDB
5. ``resnet50``        — DynSGD ResNet-50 on ImageNet-subset (bottleneck
                         blocks, 4 stages × [3,4,6,3])

All are NHWC / channels-last, end in softmax (the reference's Keras
convention — trainers swap in the on-probs loss), and lower to MXU-friendly
convs/matmuls with static shapes.
"""

from __future__ import annotations

from typing import Optional

from .layers import (Activation, AvgPool2D, BatchNorm, Conv2D, Dense, Dropout,
                     Embedding, Flatten, GlobalAvgPool2D, LSTM, MaxPool2D,
                     Residual, Sequential)
from .model import Model


def mlp_mnist(hidden: int = 500, num_classes: int = 10) -> Model:
    """MLP for flat 784-dim MNIST (reference ``examples/mnist.ipynb``
    architecture scale: Dense(500) stacks + softmax head)."""
    return Model(Sequential([
        Dense(hidden, "relu"),
        Dense(hidden, "relu"),
        Dense(num_classes, "softmax"),
    ]), input_shape=(784,), name="mlp_mnist")


def convnet_mnist(num_classes: int = 10) -> Model:
    """Small convnet for 28×28×1 MNIST (the reference notebook's convnet
    variant: conv-pool-conv-pool-dense)."""
    return Model(Sequential([
        Conv2D(32, 3, activation="relu"),
        MaxPool2D(2),
        Conv2D(64, 3, activation="relu"),
        MaxPool2D(2),
        Flatten(),
        Dense(128, "relu"),
        Dense(num_classes, "softmax"),
    ]), input_shape=(28, 28, 1), name="convnet_mnist")


def convnet_cifar10(num_classes: int = 10) -> Model:
    """VGG-ish ConvNet for 32×32×3 CIFAR-10 (ADAG benchmark config)."""
    return Model(Sequential([
        Conv2D(32, 3, activation="relu"),
        Conv2D(32, 3, activation="relu"),
        MaxPool2D(2),
        Conv2D(64, 3, activation="relu"),
        Conv2D(64, 3, activation="relu"),
        MaxPool2D(2),
        Flatten(),
        Dense(256, "relu"),
        Dropout(0.5),
        Dense(num_classes, "softmax"),
    ]), input_shape=(32, 32, 3), name="convnet_cifar10")


def _basic_block(filters: int, stride: int = 1, in_filters: int = None):
    """ResNet v1 basic block: conv-bn-relu-conv-bn (+shortcut) -relu."""
    inner = Sequential([
        Conv2D(filters, 3, strides=stride, use_bias=False),
        BatchNorm(),
        Activation("relu"),
        Conv2D(filters, 3, use_bias=False),
        BatchNorm(),
    ])
    shortcut = None
    if stride != 1 or (in_filters is not None and in_filters != filters):
        shortcut = Sequential([
            Conv2D(filters, 1, strides=stride, use_bias=False),
            BatchNorm(),
        ])
    return Residual(inner, shortcut, activation="relu")


def resnet20(num_classes: int = 10, width: int = 16) -> Model:
    """ResNet-20 for CIFAR-10 (He et al. 2015 §4.2: n=3 → 6n+2=20 layers,
    widths 16/32/64).  The DOWNPOUR benchmark config and the headline
    samples/sec/chip model.

    ``width`` scales the stage widths ``[w, 2w, 4w]`` (16 = the standard
    model).  Wider variants put MXU-granular channel counts (≥128 lanes)
    on the matmul dimensions — the scripts/mfu.py utilization ladder."""
    layers = [Conv2D(width, 3, use_bias=False), BatchNorm(),
              Activation("relu")]
    widths = [width, 2 * width, 4 * width]
    in_f = width
    for si, f in enumerate(widths):
        for bi in range(3):
            stride = 2 if (si > 0 and bi == 0) else 1
            layers.append(_basic_block(f, stride, in_f))
            in_f = f
    layers += [GlobalAvgPool2D(), Dense(num_classes, "softmax")]
    return Model(Sequential(layers), input_shape=(32, 32, 3), name="resnet20")


def _bottleneck(filters: int, stride: int = 1, in_filters: int = None):
    """ResNet v1.5 bottleneck: 1×1 reduce, 3×3 (strided), 1×1 expand ×4."""
    out_f = filters * 4
    inner = Sequential([
        Conv2D(filters, 1, use_bias=False),
        BatchNorm(),
        Activation("relu"),
        Conv2D(filters, 3, strides=stride, use_bias=False),
        BatchNorm(),
        Activation("relu"),
        Conv2D(out_f, 1, use_bias=False),
        BatchNorm(),
    ])
    shortcut = None
    if stride != 1 or (in_filters is not None and in_filters != out_f):
        shortcut = Sequential([
            Conv2D(out_f, 1, strides=stride, use_bias=False),
            BatchNorm(),
        ])
    return Residual(inner, shortcut, activation="relu")


def resnet50(num_classes: int = 1000, input_size: int = 224,
             stem: str = "conv7") -> Model:
    """ResNet-50 (DynSGD / ImageNet-subset benchmark config): stem +
    [3,4,6,3] bottleneck stages, widths 64/128/256/512.

    ``stem``: ``"conv7"`` is the classic 7×7/s2 conv + 3×3/s2 maxpool.
    ``"s2d"`` is the TPU space-to-depth stem: a 4×4 patchify
    (``SpaceToDepth``) feeding a stride-1 3×3 conv — same ×4
    downsampling and output shape, but the first contraction runs at 48
    input channels instead of 3, filling the MXU's lanes (the 7×7/s2
    stem + maxpool bound ResNet-50/96px MFU at 26%, VERDICT r3 weak #2;
    the standard MLPerf-era TPU stem rewrite)."""
    if stem == "s2d":
        from .layers import SpaceToDepth
        layers = [
            SpaceToDepth(4),
            Conv2D(64, 3, strides=1, use_bias=False),
            BatchNorm(),
            Activation("relu"),
        ]
    elif stem == "conv7":
        layers = [
            Conv2D(64, 7, strides=2, use_bias=False),
            BatchNorm(),
            Activation("relu"),
            MaxPool2D(3, strides=2, padding="SAME"),
        ]
    else:
        raise ValueError(f"stem must be 'conv7' or 's2d', got {stem!r}")
    in_f = 64
    for si, (f, blocks) in enumerate(zip([64, 128, 256, 512], [3, 4, 6, 3])):
        for bi in range(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            layers.append(_bottleneck(f, stride, in_f))
            in_f = f * 4
    layers += [GlobalAvgPool2D(), Dense(num_classes, "softmax")]
    return Model(Sequential(layers), input_shape=(input_size, input_size, 3),
                 name="resnet50")


def lstm_imdb(vocab_size: int = 20000, embed_dim: int = 128,
              lstm_units: int = 128, seq_len: int = 200) -> Model:
    """LSTM sentiment classifier for IMDB (AEASGD/EAMSGD benchmark config):
    embed → LSTM → dense sigmoid.  Sequences are padded/bucketed to
    ``seq_len`` for static shapes (XLA recompilation trap, SURVEY.md §7)."""
    return Model(Sequential([
        Embedding(vocab_size, embed_dim),
        LSTM(lstm_units),
        Dropout(0.5),
        Dense(1, "sigmoid"),
    ]), input_shape=(seq_len,), name="lstm_imdb")


def _ff_block(dim: int, ff_mult: int, moe_experts: int):
    """Transformer FF block: pre-LN residual around dense-gelu-dense, or a
    switch-MoE FF when ``moe_experts > 0`` (shared by
    ``transformer_classifier`` and ``gpt_lm``)."""
    from ..ops.attention import LayerNorm
    if moe_experts:
        from ..ops.moe import MoEDense
        ff: list = [MoEDense(moe_experts, d_hidden=dim * ff_mult)]
    else:
        ff = [Dense(dim * ff_mult, "gelu"), Dense(dim)]
    return Residual(Sequential([LayerNorm(), *ff]))


def transformer_classifier(vocab_size: int = 20000, dim: int = 128,
                           num_heads: int = 4, num_blocks: int = 2,
                           seq_len: int = 200, num_classes: int = 2,
                           ff_mult: int = 4,
                           moe_experts: int = 0) -> Model:
    """Pre-LN transformer encoder classifier — the long-context model
    family the reference never had (its sequence ceiling was one worker's
    LSTM, SURVEY.md §5.7).  Attention lowers to
    ``ops.attention.MultiHeadAttention``; for sequences sharded over an
    ``sp`` mesh axis the same math runs as ring attention
    (``parallel.ring``).

    ``moe_experts > 0`` swaps the dense FF block for a switch-MoE FF
    (``ops.moe.MoEDense`` — per-token top-1 routing; expert-sharded
    execution over an ``ep`` mesh via ``switch_moe_sharded``)."""
    from ..ops.attention import (GlobalAvgPool1D, LayerNorm,
                                 MultiHeadAttention)
    layers = [Embedding(vocab_size, dim)]
    for _ in range(num_blocks):
        layers.append(Residual(Sequential([
            LayerNorm(), MultiHeadAttention(num_heads)])))
        layers.append(_ff_block(dim, ff_mult, moe_experts))
    layers += [LayerNorm(), GlobalAvgPool1D(),
               Dense(num_classes, "softmax")]
    return Model(Sequential(layers), input_shape=(seq_len,),
                 name="transformer_classifier")


def gpt_lm(vocab_size: int = 256, dim: int = 128, num_heads: int = 4,
           num_blocks: int = 2, seq_len: int = 256, ff_mult: int = 4,
           attention_impl: str = "dense", moe_experts: int = 0,
           num_kv_heads=None, positional: str = "learned") -> Model:
    """Decoder-only causal language model (GPT-style) — the canonical
    long-context workload, beyond the reference's LSTM ceiling
    (SURVEY.md §5.7).

    Pre-LN blocks of causal ``MultiHeadAttention`` + gelu FF; ends in a
    vocab-logits Dense (no softmax — pair with
    ``loss='sparse_categorical_crossentropy'``, which averages per-token).
    Targets are the input sequence shifted left by one.

    ``attention_impl='flash'`` lowers attention to the Pallas
    VMEM-resident kernels (O(T·D) HBM fwd+bwd); for sequences past one
    chip, attach an ``sp`` mesh to every ``MultiHeadAttention`` found via
    ``model.iter_layers()`` (set ``layer.mesh = mesh``; see
    ``examples/longcontext.py``) to run ring attention over the
    sequence shards.

    ``moe_experts > 0`` swaps each dense FF block for a switch-MoE FF
    (``ops.moe.MoEDense``) — same option as
    ``transformer_classifier``."""
    from ..ops.attention import (LayerNorm, MultiHeadAttention,
                                 PositionalEmbedding)
    if positional not in ("learned", "rope"):
        raise ValueError(f"positional must be 'learned' or 'rope', got "
                         f"{positional!r}")
    rope = positional == "rope"
    layers = [Embedding(vocab_size, dim)]
    if not rope:  # rope lives inside the attention layers instead
        layers.append(PositionalEmbedding(seq_len))
    for _ in range(num_blocks):
        layers.append(Residual(Sequential([
            LayerNorm(),
            MultiHeadAttention(num_heads, causal=True,
                               impl=attention_impl,
                               num_kv_heads=num_kv_heads,
                               rope=rope)])))
        layers.append(_ff_block(dim, ff_mult, moe_experts))
    layers += [LayerNorm(), Dense(vocab_size)]
    return Model(Sequential(layers), input_shape=(seq_len,), name="gpt_lm")


def decoder_lm(vocab_size: int, hidden_size: int, num_hidden_layers: int,
               layer_types, num_attention_heads_per_layer,
               num_key_value_heads: int, head_dim: int,
               intermediate_size: int, mlp_layer_types, seq_len: int,
               sliding_window: Optional[int] = None,
               rope_parameters: Optional[dict] = None,
               gating: bool = False, rms_norm_eps: float = 1e-6,
               num_experts: int = 0, num_experts_per_tok: int = 1,
               moe_intermediate_size: int = 0,
               shared_expert_intermediate_size: int = 0,
               moe_routed_scaling_factor: float = 1.0,
               norm_topk_prob: bool = True,
               experts_held: Optional[int] = None, first_expert: int = 0,
               total_ut_steps: int = 1, early_exit_threshold: float = 1.0,
               sandwich_norm: bool = False, norm_placement: str = "pre",
               qk_norm: bool = False,
               linear_num_key_heads: int = 0,
               linear_num_value_heads: int = 0,
               linear_key_head_dim: int = 0,
               linear_value_head_dim: int = 0,
               linear_conv_kernel_dim: int = 4,
               linear_allow_neg_eigval: bool = False,
               linear_chunk_size: int = 64, linear_impl: str = "chunked",
               attention_impl: str = "dense") -> Model:
    """Decoder-only language model of the current kind, built from the
    per-layer lists a published ``config.json`` gives (the keyword names
    are that file's): pre-RMSNorm blocks ``h = x + Attn(norm(x))``,
    ``y = h + FF(norm(h))``, a final RMSNorm and an untied head without
    bias.  Logits, no softmax (``loss='sparse_categorical_crossentropy'``).

    Layer ``l`` (the first ``num_hidden_layers`` entries of each list):
    ``layer_types[l]`` is ``"full_attention"`` or ``"sliding_attention"``
    (causal, a window of ``sliding_window``), with
    ``num_attention_heads_per_layer[l]`` query heads of ``head_dim`` over
    ``num_key_value_heads`` K/V heads, rotary as ``rope_parameters[kind]``
    says (``rope_theta``, ``partial_rotary_factor``, and for
    ``rope_type: "yarn"`` the YaRN keys; a ``rope_theta`` of None: no
    positional term at all), with ``qk_norm`` an RMSNorm over all of q's
    and all of k's columns, and with ``gating`` a per-head sigmoid gate;
    or ``"linear_attention"``, a Gated DeltaNet mixer
    (``ops.gated_delta.GatedDeltaNet``: ``linear_num_key_heads`` heads,
    as many value heads, keys of ``linear_key_head_dim`` and values of
    ``linear_value_head_dim``, ``linear_conv_kernel_dim`` taps, beta in
    (0, 2) with ``linear_allow_neg_eigval``, the rule in chunks of
    ``linear_chunk_size`` by ``linear_impl``); ``mlp_layer_types[l]`` is
    ``"dense"`` (SwiGLU of width
    ``intermediate_size``) or ``"sparse"`` (``ops.moe.SparseMoE``: top
    ``num_experts_per_tok`` of ``num_experts`` SwiGLU experts of width
    ``moe_intermediate_size``, weights normalised if ``norm_topk_prob``
    and times ``moe_routed_scaling_factor``, plus a shared expert of
    width ``shared_expert_intermediate_size``).

    ``experts_held`` / ``first_expert``: one chip's share of an
    expert-parallel deployment — every sparse layer routes over all
    ``num_experts`` and holds (has parameters for, computes) only the
    ``experts_held`` from ``first_expert``; default all.

    ``sandwich_norm``: a second RMSNorm on each sublayer's output, before
    it is added: ``h = x + norm(Attn(norm(x)))``, ``y = h + norm(FF(
    norm(h)))``.  ``norm_placement="post"`` (the Olmo 2 family's): the
    sublayer's output alone is normed, ``h = x + norm(Attn(x))``, ``y = h
    + norm(FF(h))``.  ``total_ut_steps`` > 1 (the ``ouro`` family's keys): the
    layers and the final norm run that many times over ONE set of
    parameters, each pass on the one before's normed output
    (``layers.Looped``), and one head and one exit gate read every pass
    (``layers.ExitHeads``): the output is ``{"logits": one array a pass,
    "exit_gate": (B, T, passes)}`` (``loss="exit_weighted_crossentropy"``),
    and ``Model.decode_logits`` gives each token the first pass whose
    exit chances reach ``early_exit_threshold``."""
    from ..ops.attention import MultiHeadAttention
    from ..ops.moe import SparseMoE
    from .layers import ExitHeads, Looped, RMSNorm, SwiGLU
    rope_parameters = rope_parameters or {}
    if norm_placement not in ("pre", "post"):
        raise ValueError(f"norm_placement must be 'pre' or 'post', got "
                         f"{norm_placement!r}")

    def block(mixer):
        if norm_placement == "post":
            return Residual(Sequential([mixer, RMSNorm(rms_norm_eps)]))
        after = [RMSNorm(rms_norm_eps)] if sandwich_norm else []
        return Residual(Sequential([RMSNorm(rms_norm_eps), mixer, *after]))

    def linear_attention():
        from ..ops.gated_delta import GatedDeltaNet
        if linear_num_value_heads != linear_num_key_heads:
            raise ValueError(
                f"{linear_num_value_heads} value heads over "
                f"{linear_num_key_heads} key heads: only equal counts are "
                f"built")
        return GatedDeltaNet(
            linear_num_key_heads, linear_key_head_dim, linear_value_head_dim,
            conv_kernel=linear_conv_kernel_dim, chunk_size=linear_chunk_size,
            norm_eps=rms_norm_eps, allow_neg_eigval=linear_allow_neg_eigval,
            impl=linear_impl)

    layers = []
    for kind, heads, mlp in list(zip(layer_types,
                                     num_attention_heads_per_layer,
                                     mlp_layer_types))[:num_hidden_layers]:
        if kind not in ("full_attention", "sliding_attention",
                        "linear_attention"):
            raise ValueError(f"unknown layer type {kind!r}")
        rope = dict(rope_parameters.get(kind, {}))
        positions = rope.get("rope_theta", 10000.0) is not None
        attention = linear_attention() if kind == "linear_attention" \
            else MultiHeadAttention(
                heads, causal=True, impl=attention_impl,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rope=positions,
                window=sliding_window if kind == "sliding_attention"
                else None,
                rope_theta=rope.get("rope_theta", 10000.0) if positions
                else 10000.0,
                rope_fraction=rope.get("partial_rotary_factor", 1.0),
                rope_scaling=(rope or None) if positions else None,
                gate=gating, qk_norm=qk_norm, norm_eps=rms_norm_eps)
        if mlp == "dense":
            ff = SwiGLU(intermediate_size)
        elif mlp == "sparse":
            ff = SparseMoE(num_experts, num_experts_per_tok,
                           moe_intermediate_size,
                           shared_hidden=shared_expert_intermediate_size,
                           routed_scale=moe_routed_scaling_factor,
                           normalise=norm_topk_prob,
                           experts_held=experts_held,
                           first_expert=first_expert)
        else:
            raise ValueError(f"unknown mlp layer type {mlp!r}")
        layers += [block(attention), block(ff)]
    embedding, norm = Embedding(vocab_size, hidden_size), \
        RMSNorm(rms_norm_eps)
    if total_ut_steps > 1:
        layers = [embedding, Looped(layers, total_ut_steps, norm),
                  ExitHeads(vocab_size, early_exit_threshold)]
    else:
        layers = [embedding, *layers, norm,
                  Dense(vocab_size, use_bias=False)]
    return Model(Sequential(layers), input_shape=(seq_len,),
                 name="decoder_lm")


def hybrid_lm(vocab_size: int, hidden_size: int, num_hidden_layers: int,
              hybrid_override_pattern: str, seq_len: int,
              mamba_num_heads: int = 0, mamba_head_dim: int = 0,
              ssm_state_size: int = 0, n_groups: int = 1,
              conv_kernel: int = 4, chunk_size: int = 128,
              num_attention_heads: int = 0, num_key_value_heads: int = 0,
              head_dim: int = 0, intermediate_size: int = 0,
              n_routed_experts: int = 0, num_experts_per_tok: int = 1,
              moe_intermediate_size: int = 0,
              moe_shared_expert_intermediate_size: int = 0,
              routed_scaling_factor: float = 1.0,
              norm_topk_prob: bool = True,
              layer_norm_epsilon: float = 1e-5,
              experts_held: Optional[int] = None, first_expert: int = 0,
              attention_impl: str = "dense",
              ssm_impl: str = "chunked") -> Model:
    """Decoder-only language model with ONE mixer a layer, by pattern (the
    ``nemotron_h`` family; the keyword names are its ``config.json``'s):
    layer l is ``x + mixer_l(RMSNorm(x))``, a final RMSNorm and an untied
    head follow, and no linear layer has a bias.  Logits, no softmax.

    ``hybrid_override_pattern[l]`` (its first ``num_hidden_layers``
    letters are built) names the mixer: ``M`` a Mamba-2 mixer
    (``ops.ssm.Mamba2Mixer``: ``mamba_num_heads`` heads of
    ``mamba_head_dim``, state ``ssm_state_size``, ``n_groups``,
    ``conv_kernel`` taps, the scan in chunks of ``chunk_size`` by
    ``ssm_impl``); ``*`` causal attention of ``num_attention_heads`` query
    heads of ``head_dim`` over ``num_key_value_heads`` K/V heads, with no
    positional term of any kind; ``E`` a mixture of experts
    (``ops.moe.SparseMoE``: sigmoid scores with a choice-only bias, top
    ``num_experts_per_tok`` of ``n_routed_experts`` relu² experts of width
    ``moe_intermediate_size``, weights normalised if ``norm_topk_prob``
    and times ``routed_scaling_factor``, plus a shared expert of width
    ``moe_shared_expert_intermediate_size``); ``-`` a dense relu² MLP of
    width ``intermediate_size``.

    ``experts_held`` / ``first_expert``: one chip's share of an
    expert-parallel deployment, as in :func:`decoder_lm`."""
    from ..ops.attention import MultiHeadAttention
    from ..ops.moe import SparseMoE
    from ..ops.ssm import Mamba2Mixer
    from .layers import RMSNorm

    def mixer(kind: str):
        if kind == "M":
            return Mamba2Mixer(mamba_num_heads, mamba_head_dim,
                               ssm_state_size, n_groups=n_groups,
                               conv_kernel=conv_kernel,
                               chunk_size=chunk_size,
                               norm_eps=layer_norm_epsilon, impl=ssm_impl)
        if kind == "*":
            return MultiHeadAttention(
                num_attention_heads, causal=True, impl=attention_impl,
                num_kv_heads=num_key_value_heads, head_dim=head_dim,
                rope=False)
        if kind == "E":
            return SparseMoE(
                n_routed_experts, num_experts_per_tok, moe_intermediate_size,
                shared_hidden=moe_shared_expert_intermediate_size,
                routed_scale=routed_scaling_factor, normalise=norm_topk_prob,
                experts_held=experts_held, first_expert=first_expert,
                expert_activation="relu2", scoring="sigmoid")
        if kind == "-":
            return Sequential([
                Dense(intermediate_size, "relu2", use_bias=False),
                Dense(hidden_size, use_bias=False)])
        raise ValueError(f"unknown layer kind {kind!r} in "
                         f"hybrid_override_pattern (M, E, * or -)")

    if num_hidden_layers > len(hybrid_override_pattern):
        raise ValueError(f"{num_hidden_layers} layers of a pattern of "
                         f"{len(hybrid_override_pattern)}")
    layers = [Embedding(vocab_size, hidden_size)]
    for kind in hybrid_override_pattern[:num_hidden_layers]:
        layers.append(Residual(Sequential([RMSNorm(layer_norm_epsilon),
                                           mixer(kind)])))
    layers += [RMSNorm(layer_norm_epsilon),
               Dense(vocab_size, use_bias=False)]
    return Model(Sequential(layers), input_shape=(seq_len,),
                 name="hybrid_lm")


def draft_lm(target: Model, dim: int = 32, num_heads: int = 2,
             num_blocks: int = 1, ff_mult: int = 4,
             positional: str = "learned") -> Model:
    """A small **draft** model for speculative decoding (ISSUE 11),
    shape-compatible with a ``gpt_lm`` ``target`` by construction: same
    vocab (proposals are verified token-by-token in one shared id
    space) and same ``seq_len`` (the draft's KV cache tracks the same
    absolute positions as the target's), everything else scaled down.
    ``DecodeEngine(..., draft_model=..., draft_variables=...)`` verifies
    exactly these two invariants at construction — this helper makes
    them impossible to get wrong.

    The draft's *weights* are the caller's problem (typically a
    distillation of the target): speculative decoding is greedy-exact at
    ANY draft quality, a bad draft only costs accept rate."""
    return gpt_lm(vocab_size=int(target.output_shape[-1]), dim=dim,
                  num_heads=num_heads, num_blocks=num_blocks,
                  seq_len=int(target.input_shape[0]), ff_mult=ff_mult,
                  positional=positional)


ZOO = {
    "mlp_mnist": mlp_mnist,
    "convnet_mnist": convnet_mnist,
    "convnet_cifar10": convnet_cifar10,
    "resnet20": resnet20,
    "resnet50": resnet50,
    "lstm_imdb": lstm_imdb,
    "transformer_classifier": transformer_classifier,
    "gpt_lm": gpt_lm,
}
