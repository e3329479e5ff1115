"""Model zoo matching the reference example workloads (SURVEY.md §2.1 row 23,
``BASELINE.json.configs``): MNIST MLP, MNIST ConvNet, CIFAR-10 ConvNet, and
the ATLAS Higgs tabular MLP.  Architectures follow the reference notebooks'
shapes (Dense-500/Conv-32 scale models); exact layer dims are ours.
"""

from __future__ import annotations

from ..core import (Sequential, Dense, Conv2D, MaxPooling2D, Flatten, Reshape,
                    Dropout)
from ..core.layers import (Embedding, PositionalEmbedding, TransformerBlock,
                           LayerNormalization, RMSNorm, GatedAttention,
                           KimiDeltaAttention, Mamba2Mixer,
                           MultiHeadAttention, SparseMoE, GatedMLP,
                           HybridBlock, TiedHead)


def mnist_mlp(compute_dtype: str = "bfloat16") -> Sequential:
    """MLP on flat 784-dim MNIST rows (reference ``examples/mnist.ipynb``
    MLP variant / workflow.ipynb-style two-hidden-layer net)."""
    return Sequential([
        Dense(500, activation="relu"),
        Dense(500, activation="relu"),
        Dense(10, activation="softmax"),
    ], input_shape=(784,), compute_dtype=compute_dtype, name="mnist_mlp")


def mnist_convnet(compute_dtype: str = "bfloat16") -> Sequential:
    """ConvNet on 28x28x1 MNIST (the ADAG north-star benchmark model;
    reference ``examples/mnist.ipynb`` ConvNet)."""
    return Sequential([
        Reshape((28, 28, 1)),
        Conv2D(32, 3, activation="relu"),
        Conv2D(32, 3, activation="relu"),
        MaxPooling2D(2),
        Conv2D(64, 3, activation="relu"),
        MaxPooling2D(2),
        Flatten(),
        Dense(128, activation="relu"),
        Dense(10, activation="softmax"),
    ], input_shape=(784,), compute_dtype=compute_dtype, name="mnist_convnet")


def digits_mlp(compute_dtype: str = "bfloat16") -> Sequential:
    """MLP on the REAL sklearn-digits workload (64-dim 8x8 images — see
    ``data.datasets.load_digits``): the accuracy-parity artifact's real-data
    model, sized down from ``mnist_mlp`` for the smaller input."""
    return Sequential([
        Dense(128, activation="relu"),
        Dense(128, activation="relu"),
        Dense(10, activation="softmax"),
    ], input_shape=(64,), compute_dtype=compute_dtype, name="digits_mlp")


def digits_convnet(compute_dtype: str = "bfloat16") -> Sequential:
    """ConvNet on the REAL sklearn-digits workload: flat 64-dim rows
    reshaped to 8x8x1 through a small Conv2D stack — the conv analogue of
    ``digits_mlp`` so the real-pixel accuracy-parity gate covers the
    north-star MODEL FAMILY (MNIST ConvNet, SURVEY.md §6), not just an
    MLP.  'same' padding keeps the tiny 8x8 plane from vanishing before
    the pool."""
    return Sequential([
        Reshape((8, 8, 1)),
        Conv2D(16, 3, activation="relu", padding="same"),
        Conv2D(16, 3, activation="relu", padding="same"),
        MaxPooling2D(2),
        Conv2D(32, 3, activation="relu", padding="same"),
        MaxPooling2D(2),
        Flatten(),
        Dense(64, activation="relu"),
        Dense(10, activation="softmax"),
    ], input_shape=(64,), compute_dtype=compute_dtype,
        name="digits_convnet")


def cifar10_convnet(compute_dtype: str = "bfloat16") -> Sequential:
    """Small ConvNet on 32x32x3 CIFAR-10 (reference DOWNPOUR config)."""
    return Sequential([
        Reshape((32, 32, 3)),
        Conv2D(32, 3, activation="relu"),
        Conv2D(32, 3, activation="relu"),
        MaxPooling2D(2),
        Conv2D(64, 3, activation="relu"),
        Conv2D(64, 3, activation="relu"),
        MaxPooling2D(2),
        Flatten(),
        Dense(256, activation="relu"),
        Dropout(0.5),
        Dense(10, activation="softmax"),
    ], input_shape=(3072,), compute_dtype=compute_dtype,
        name="cifar10_convnet")


def higgs_mlp(compute_dtype: str = "bfloat16") -> Sequential:
    """Tabular MLP for ATLAS Higgs signal/background (reference
    ``examples/workflow.ipynb``: Dense-500/relu stack, 2-way softmax)."""
    return Sequential([
        Dense(500, activation="relu"),
        Dense(500, activation="relu"),
        Dense(2, activation="softmax"),
    ], input_shape=(28,), compute_dtype=compute_dtype, name="higgs_mlp")


def transformer_lm(vocab_size: int = 256, seq_len: int = 128,
                   d_model: int = 128, num_heads: int = 4,
                   num_layers: int = 2, mlp_dim: int = 512,
                   dropout: float = 0.0, compute_dtype: str = "bfloat16",
                   attention_impl=None, num_kv_heads=None,
                   attention_window=None,
                   positional: str = "learned",
                   rope_theta: float = 10000.0,
                   rope_scale: float = 1.0) -> Sequential:
    """Decoder-only causal transformer LM — the long-context flagship.

    No reference counterpart (SURVEY.md §2.3: attention/sequence models are
    absent upstream); this model family exists so the framework's sequence-
    parallel path (ring attention over a 'seq' mesh axis) has a first-class
    workload.  Input: (seq_len,) int token ids; output: (seq_len, vocab)
    logits — train with loss="sparse_categorical_crossentropy_from_logits".
    """
    if positional not in ("learned", "rope"):
        raise ValueError(f"positional must be 'learned' or 'rope', got "
                         f"{positional!r}")
    rope = positional == "rope"
    layers = [Embedding(vocab_size, d_model)]
    if not rope:  # RoPE rotates q/k inside attention; no additive table
        layers.append(PositionalEmbedding(seq_len))
    for _ in range(num_layers):
        layers.append(TransformerBlock(
            num_heads, d_model // num_heads, mlp_dim, dropout=dropout,
            causal=True, attention_impl=attention_impl,
            num_kv_heads=num_kv_heads, attention_window=attention_window,
            rope=rope, rope_theta=rope_theta, rope_scale=rope_scale))
    layers += [LayerNormalization(), Dense(vocab_size)]
    return Sequential(layers, input_shape=(seq_len,),
                      compute_dtype=compute_dtype, name="transformer_lm")


def _interleaved_blocks(config: dict, held):
    """The blocks of a config of the Solar-Open2 kind: every layer a mixer
    (``gqa_layers``: gated NoPE GQA; the others Kimi Delta Attention) THEN
    sparse experts."""
    if config.get("use_rope", False):
        raise ValueError("hybrid_lm builds NoPE attention (use_rope false); "
                         "this config asks for rotary positions")
    if not config.get("use_gqa_gate", True):
        raise ValueError("hybrid_lm builds gated attention (use_gqa_gate)")
    if int(config.get("first_k_dense_replace", 0)):
        raise ValueError("hybrid_lm builds sparse experts in every layer "
                         "(first_k_dense_replace 0); this config asks for "
                         "dense feed-forward layers first")
    eps = float(config["rms_norm_eps"])
    lin = config["linear_attn_config"]
    gqa = {int(i) for i in config["gqa_layers"]}
    experts = int(config["n_routed_experts"])
    moe_dim = int(config["moe_intermediate_size"])
    for i in range(int(config["num_hidden_layers"])):
        if i in gqa:
            mixer = GatedAttention(int(config["num_attention_heads"]),
                                   int(config["head_dim"]),
                                   int(config["num_key_value_heads"]))
        else:
            mixer = KimiDeltaAttention(
                int(lin["num_heads"]), int(lin["head_dim"]),
                conv_size=int(lin["short_conv_kernel_size"]),
                gate_rank=int(lin["head_dim"]),
                neg_eigval=bool(config.get("kda_allow_neg_eigval", False)),
                norm_eps=eps)
        ffn = SparseMoE(
            experts, int(config["num_experts_per_tok"]), moe_dim, held=held,
            shared_dim=int(config.get("n_shared_experts", 0)) * moe_dim)
        yield HybridBlock(mixer, ffn, epsilon=eps)


def _pattern_blocks(config: dict, held):
    """The blocks of a config of the ``nemotron_h`` kind: layer ``l`` is ONE
    part, named by ``hybrid_override_pattern[l]`` — ``M`` a Mamba-2 mixer,
    ``*`` causal NoPE grouped-query attention, ``E`` sparse experts (sigmoid
    scores, a selection bias, ``routed_scaling_factor``; ungated relu^2
    experts and shared expert).  The first ``num_hidden_layers`` characters
    are built, so a cut in depth keeps the published pattern."""
    pattern = str(config["hybrid_override_pattern"])
    n = int(config["num_hidden_layers"])
    if n > len(pattern):
        raise ValueError(f"num_hidden_layers={n} but hybrid_override_pattern "
                         f"names {len(pattern)} layers")
    if config.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError("hybrid_lm builds relu2 experts; this config asks "
                         f"for {config['mlp_hidden_act']!r}")
    if config.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError("hybrid_lm builds SiLU state-space layers; this "
                         f"config asks for {config['mamba_hidden_act']!r}")
    for key in ("use_bias", "attention_bias", "mlp_bias", "mamba_proj_bias"):
        if config.get(key, False):
            raise ValueError(f"hybrid_lm builds bias-free projections; this "
                             f"config sets {key}")
    if not config.get("use_conv_bias", True):
        raise ValueError("hybrid_lm builds the state-space convolution with "
                         "its bias (use_conv_bias)")
    if int(config.get("n_group", 1)) != 1 or \
            int(config.get("topk_group", 1)) != 1:
        raise ValueError("hybrid_lm builds a router without expert groups "
                         "(n_group 1, topk_group 1)")
    if not config.get("norm_topk_prob", True):
        raise ValueError("hybrid_lm builds a router whose chosen weights "
                         "are renormalised (norm_topk_prob)")
    eps = float(config["layer_norm_epsilon"])
    moe_dim = int(config["moe_intermediate_size"])
    parts = {
        "M": lambda: dict(mixer=Mamba2Mixer(
            int(config["mamba_num_heads"]), int(config["mamba_head_dim"]),
            int(config["ssm_state_size"]), num_groups=int(config["n_groups"]),
            conv_size=int(config["conv_kernel"]),
            chunk_size=int(config["chunk_size"]), norm_eps=eps)),
        "*": lambda: dict(mixer=MultiHeadAttention(
            int(config["num_attention_heads"]), int(config["head_dim"]),
            causal=True, use_bias=False,
            num_kv_heads=int(config["num_key_value_heads"]))),
        "E": lambda: dict(ffn=SparseMoE(
            int(config["n_routed_experts"]),
            int(config["num_experts_per_tok"]), moe_dim, held=held,
            shared_dim=int(config.get("n_shared_experts", 0)) * int(
                config.get("moe_shared_expert_intermediate_size", moe_dim)),
            router="sigmoid_bias",
            router_scale=float(config.get("routed_scaling_factor", 1.0)),
            expert_form="relu2")),
    }
    for i, ch in enumerate(pattern[:n]):
        if ch not in parts:
            raise ValueError(
                f"hybrid_override_pattern[{i}] = {ch!r}: hybrid_lm builds "
                "'M' (Mamba-2), '*' (attention) and 'E' (sparse experts); "
                "a dense MLP layer ('-') is not written")
        yield HybridBlock(epsilon=eps, **parts[ch]())


def _typed_blocks(config: dict):
    """The blocks of a config of the ``granitemoehybrid`` kind: layer ``l``
    is a mixer named by ``layer_types[l]`` (``mamba``: a Mamba-2 mixer from
    the ``mamba_*`` keys; ``attention``: causal NoPE grouped-query attention
    whose scores are multiplied by ``attention_multiplier``) THEN a dense
    gated-SiLU MLP of ``shared_intermediate_size``, each part's output
    times ``residual_multiplier`` before it joins the stream."""
    types = list(config["layer_types"])
    n = int(config["num_hidden_layers"])
    if n > len(types):
        raise ValueError(f"num_hidden_layers={n} but layer_types names "
                         f"{len(types)} layers")
    if int(config.get("num_local_experts", 0)):
        raise ValueError("hybrid_lm builds a dense MLP in every block of a "
                         "layer_types config (num_local_experts 0); routed "
                         "experts beside the shared MLP are not written")
    if config.get("position_embedding_type", "nope") != "nope":
        raise ValueError(
            "hybrid_lm builds NoPE attention (position_embedding_type "
            f"'nope'); this config asks for "
            f"{config['position_embedding_type']!r}")
    for key in ("attention_bias", "mamba_proj_bias"):
        if config.get(key, False):
            raise ValueError(f"hybrid_lm builds bias-free projections; this "
                             f"config sets {key}")
    if not config.get("mamba_conv_bias", True):
        raise ValueError("hybrid_lm builds the state-space convolution with "
                         "its bias (mamba_conv_bias)")
    if config.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError(
            "hybrid_lm builds RMSNorm (normalization_function 'rmsnorm'); "
            f"this config asks for {config['normalization_function']!r}")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("hybrid_lm builds gated-SiLU MLPs and SiLU "
                         "state-space layers (hidden_act 'silu'); this "
                         f"config asks for {config['hidden_act']!r}")
    d = int(config["hidden_size"])
    m_heads, m_dim = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    if int(config["mamba_expand"]) * d != m_heads * m_dim:
        raise ValueError(
            f"mamba_expand x hidden_size = {int(config['mamba_expand']) * d} "
            f"but mamba_n_heads x mamba_d_head = {m_heads * m_dim}")
    eps = float(config["rms_norm_eps"])
    heads = int(config["num_attention_heads"])
    parts = {
        "mamba": lambda: Mamba2Mixer(
            m_heads, m_dim, int(config["mamba_d_state"]),
            num_groups=int(config["mamba_n_groups"]),
            conv_size=int(config["mamba_d_conv"]),
            chunk_size=int(config["mamba_chunk_size"]), norm_eps=eps),
        "attention": lambda: MultiHeadAttention(
            heads, int(config.get("head_dim") or d // heads), causal=True,
            use_bias=False, num_kv_heads=int(config["num_key_value_heads"]),
            score_scale=config.get("attention_multiplier")),
    }
    for i, kind in enumerate(types[:n]):
        if kind not in parts:
            raise ValueError(f"layer_types[{i}] = {kind!r}: hybrid_lm builds "
                             "'mamba' (Mamba-2) and 'attention'")
        yield HybridBlock(
            parts[kind](), GatedMLP(int(config["shared_intermediate_size"])),
            epsilon=eps,
            residual_multiplier=float(config.get("residual_multiplier", 1.0)))


def hybrid_lm(config: dict, compute_dtype: str = "bfloat16",
              held=None) -> Sequential:
    """A decoder-only LM of hybrid blocks, built from the keys of a published
    ``config.json``.  Three kinds of stack, told apart by the config's own
    keys:

    - ``layer_types`` (``model_type`` ``granitemoehybrid``): every layer is
      a mixer, ``mamba`` (``mamba_n_heads``, ``mamba_d_head``,
      ``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``,
      ``mamba_chunk_size``; ``mamba_expand``) or ``attention`` (NoPE
      grouped-query, scores times ``attention_multiplier``), THEN a dense
      gated-SiLU MLP (``shared_intermediate_size``); the embedding is
      multiplied by ``embedding_multiplier``, each residual branch by
      ``residual_multiplier``, the logits divided by ``logits_scaling``
      (``_typed_blocks``, which also names what it refuses: routed experts,
      a position signal, biases, another norm);
    - ``hybrid_override_pattern`` (``model_type`` ``nemotron_h``): every
      layer is ONE residual part, a Mamba-2 mixer (``mamba_num_heads``,
      ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
      ``chunk_size``), NoPE grouped-query attention, or sparse experts with
      a sigmoid router, a selection bias and ungated relu^2 experts
      (``_pattern_blocks``);
    - otherwise the Solar-Open2 kind: ``gqa_layers`` names the layers
      whose mixer is gated NoPE grouped-query attention
      (``num_attention_heads`` over ``num_key_value_heads`` of ``head_dim``,
      ``use_gqa_gate``); every other layer's is Kimi Delta Attention
      (``linear_attn_config``: ``num_heads``, ``head_dim``,
      ``short_conv_kernel_size``; ``kda_allow_neg_eigval``;
      ``kda_use_full_proj`` false: its decay and output gates are low-rank
      through ``head_dim``), and every layer has sparse experts after its
      mixer (``n_routed_experts``, ``num_experts_per_tok``,
      ``moe_intermediate_size``, ``n_shared_experts``;
      ``first_k_dense_replace`` 0).

    RMSNorm everywhere, no position signal, no biases.  The head is the
    embedding table itself where the config says ``tie_word_embeddings``
    (``TiedHead``: one table in the parameters), a ``Dense`` of its own
    otherwise.

    ``held`` = (first, count): the experts whose weights live here, of the
    ``n_routed_experts`` the router scores — one chip's share of an
    expert-parallel deployment (default: all).  ``num_hidden_layers`` and
    ``vocab_size`` are taken as given, so a configuration cut in depth or to
    a slice of the vocabulary builds as it reads."""
    d, vocab = int(config["hidden_size"]), int(config["vocab_size"])
    if "layer_types" in config:
        blocks, eps_key = _typed_blocks(config), "rms_norm_eps"
    elif "hybrid_override_pattern" in config:
        blocks, eps_key = _pattern_blocks(config, held), "layer_norm_epsilon"
    else:
        blocks, eps_key = _interleaved_blocks(config, held), "rms_norm_eps"
    divisor = float(config.get("logits_scaling", 1.0))
    if config.get("tie_word_embeddings", False):
        head = TiedHead(vocab, tied_to=0, divisor=divisor)
    elif divisor != 1.0:
        raise ValueError("hybrid_lm divides the logits (logits_scaling) of a "
                         "tied head only; this config's head is untied")
    else:
        head = Dense(vocab, use_bias=False)
    embed = Embedding(vocab, d, output_scale=float(
        config.get("embedding_multiplier", 1.0)))
    layers = [embed, *blocks, RMSNorm(float(config[eps_key])), head]
    return Sequential(layers, input_shape=(8,), compute_dtype=compute_dtype,
                      name="hybrid_lm")
