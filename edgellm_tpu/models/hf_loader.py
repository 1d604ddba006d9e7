"""Convert HuggingFace checkpoints (torch state_dicts) into the stacked pytree layout.

The reference leans on ``AutoModelForCausalLM.from_pretrained`` at runtime and keeps
*two* live torch model instances per experiment (``pythia_model.py:25``,
``last_row_exp.py:66-70``). Here conversion happens once: a torch state_dict (from a
downloaded checkpoint, or a randomly-initialized ``transformers`` model in offline
test environments) becomes a single JAX pytree with layers stacked on axis 0, ready
to be sharded along a pipeline-stage mesh axis.

Layout notes:
- torch ``nn.Linear.weight`` is (out, in); we store (in, out) so the forward is
  ``x @ W``.
- GPT-NeoX fuses QKV with per-head interleaving: ``query_key_value.weight`` viewed
  as (num_heads, 3*head_dim, in) splits into q/k/v as the three head_dim-blocks of
  each head's rows (matches HF's ``qkv.view(..., num_heads, 3*head_size)`` split).
"""
from __future__ import annotations

import logging
import math
import os
import time

import numpy as np
import jax.numpy as jnp

from .configs import ModelConfig


def fetch_with_retry(url: str, dest: str, *, max_retries: int = 4,
                     timeout: float = 30.0, backoff: float = 1.0,
                     _sleep=time.sleep) -> str:
    """Download ``url`` to ``dest`` with bounded retries and exponential
    backoff — the edge-network counterpart of the wire-fault layer: flaky
    checkpoint links get ``max_retries`` re-attempts (waiting ``backoff * 2**n``
    seconds between them), permanent HTTP client errors (4xx) fail immediately,
    and the final error says exactly what to do next. The download lands in a
    temp file and is renamed into place, so a cut connection never leaves a
    truncated ``dest`` behind. stdlib urllib only — no new dependencies."""
    import urllib.error
    import urllib.request

    if max_retries < 0:
        raise ValueError(f"max_retries must be >= 0, got {max_retries}")
    os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
    tmp = dest + ".part"
    last_err = None
    for attempt in range(max_retries + 1):
        try:
            with urllib.request.urlopen(url, timeout=timeout) as r, \
                    open(tmp, "wb") as f:
                while chunk := r.read(1 << 20):
                    f.write(chunk)
            os.replace(tmp, dest)
            return dest
        except urllib.error.HTTPError as e:
            if e.code < 500:  # 4xx is permanent; retrying can't fix a 404
                raise RuntimeError(
                    f"fetch of {url} failed permanently (HTTP {e.code} "
                    f"{e.reason}); check the URL/revision, or download the "
                    f"file manually and pass its local path") from e
            last_err = e
        except (urllib.error.URLError, TimeoutError, OSError) as e:
            last_err = e
        if attempt < max_retries:
            _sleep(backoff * (2 ** attempt))
    raise RuntimeError(
        f"fetch of {url} failed after {max_retries + 1} attempts "
        f"(last error: {last_err}); the link may be down — retry later, or "
        f"download the file manually and pass its local path") from last_err


def _np(t):
    if hasattr(t, "detach"):
        t = t.detach().cpu().float().numpy()
    return np.asarray(t, dtype=np.float32)


def _stack(sd, template: str, n: int, transform):
    return jnp.asarray(np.stack([transform(_np(sd[template.format(i=i)])) for i in range(n)]))


def _split_neox_qkv(w: np.ndarray, cfg: ModelConfig):
    """(3D, in)-shaped fused weight -> (q, k, v) each (in, D)."""
    h, hd = cfg.num_heads, cfg.head_dim
    per_head = w.reshape(h, 3, hd, -1)
    return tuple(per_head[:, j].reshape(h * hd, -1).T for j in range(3))


def _split_neox_qkv_bias(b: np.ndarray, cfg: ModelConfig):
    h, hd = cfg.num_heads, cfg.head_dim
    per_head = b.reshape(h, 3, hd)
    return tuple(per_head[:, j].reshape(h * hd) for j in range(3))


def params_from_state_dict(cfg: ModelConfig, sd: dict) -> dict:
    """Build the framework's parameter pytree from a HF torch state_dict."""
    if cfg.family == "gpt_neox":
        return _neox_params(cfg, sd)
    if cfg.family == "lfm2_moe":
        return _lfm2_moe_params(cfg, sd)
    if cfg.family == "keye_vl2":
        return _keye_vl2_params(cfg, sd)
    if cfg.family == "deepseek_v32":
        return _deepseek_v32_params(cfg, sd)
    if cfg.family == "dots3_note":
        return _dots3_note_params(cfg, sd)
    if cfg.is_hybrid:
        raise ValueError(
            f"no state_dict mapping for family {cfg.family!r}: its parameters "
            f"are held per layer kind (models/hybrid.py) and only "
            f"config_from_hf knows the family")
    return _qwen2_params(cfg, sd)


def _neox_params(cfg: ModelConfig, sd: dict) -> dict:
    L = cfg.num_layers
    qs, ks, vs, qbs, kbs, vbs = [], [], [], [], [], []
    for i in range(L):
        w = _np(sd[f"gpt_neox.layers.{i}.attention.query_key_value.weight"])
        b = _np(sd[f"gpt_neox.layers.{i}.attention.query_key_value.bias"])
        q, k, v = _split_neox_qkv(w, cfg)
        qb, kb, vb = _split_neox_qkv_bias(b, cfg)
        qs.append(q); ks.append(k); vs.append(v)
        qbs.append(qb); kbs.append(kb); vbs.append(vb)
    lt = "gpt_neox.layers.{i}."
    layers = {
        "wq": jnp.asarray(np.stack(qs)), "wk": jnp.asarray(np.stack(ks)),
        "wv": jnp.asarray(np.stack(vs)),
        "bq": jnp.asarray(np.stack(qbs)), "bk": jnp.asarray(np.stack(kbs)),
        "bv": jnp.asarray(np.stack(vbs)),
        "wo": _stack(sd, lt + "attention.dense.weight", L, lambda w: w.T),
        "bo": _stack(sd, lt + "attention.dense.bias", L, lambda b: b),
        "ln1_scale": _stack(sd, lt + "input_layernorm.weight", L, lambda w: w),
        "ln1_bias": _stack(sd, lt + "input_layernorm.bias", L, lambda w: w),
        "ln2_scale": _stack(sd, lt + "post_attention_layernorm.weight", L, lambda w: w),
        "ln2_bias": _stack(sd, lt + "post_attention_layernorm.bias", L, lambda w: w),
        "w_in": _stack(sd, lt + "mlp.dense_h_to_4h.weight", L, lambda w: w.T),
        "b_in": _stack(sd, lt + "mlp.dense_h_to_4h.bias", L, lambda b: b),
        "w_out": _stack(sd, lt + "mlp.dense_4h_to_h.weight", L, lambda w: w.T),
        "b_out": _stack(sd, lt + "mlp.dense_4h_to_h.bias", L, lambda b: b),
    }
    params = {
        "embed": jnp.asarray(_np(sd["gpt_neox.embed_in.weight"])),
        "layers": layers,
        "final_norm_scale": jnp.asarray(_np(sd["gpt_neox.final_layer_norm.weight"])),
        "final_norm_bias": jnp.asarray(_np(sd["gpt_neox.final_layer_norm.bias"])),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(_np(sd["embed_out.weight"]).T)
    return params


def _qwen2_params(cfg: ModelConfig, sd: dict) -> dict:
    """Qwen2 and Llama share HF key names; Llama simply has no QKV biases."""
    L = cfg.num_layers
    lt = "model.layers.{i}."
    layers = {
        "wq": _stack(sd, lt + "self_attn.q_proj.weight", L, lambda w: w.T),
        "wk": _stack(sd, lt + "self_attn.k_proj.weight", L, lambda w: w.T),
        "wv": _stack(sd, lt + "self_attn.v_proj.weight", L, lambda w: w.T),
        "wo": _stack(sd, lt + "self_attn.o_proj.weight", L, lambda w: w.T),
        "ln1_scale": _stack(sd, lt + "input_layernorm.weight", L, lambda w: w),
        "ln2_scale": _stack(sd, lt + "post_attention_layernorm.weight", L, lambda w: w),
        "w_gate": _stack(sd, lt + "mlp.gate_proj.weight", L, lambda w: w.T),
        "w_up": _stack(sd, lt + "mlp.up_proj.weight", L, lambda w: w.T),
        "w_down": _stack(sd, lt + "mlp.down_proj.weight", L, lambda w: w.T),
    }
    if cfg.qkv_bias:
        layers.update({
            "bq": _stack(sd, lt + "self_attn.q_proj.bias", L, lambda b: b),
            "bk": _stack(sd, lt + "self_attn.k_proj.bias", L, lambda b: b),
            "bv": _stack(sd, lt + "self_attn.v_proj.bias", L, lambda b: b),
        })
    params = {
        "embed": jnp.asarray(_np(sd["model.embed_tokens.weight"])),
        "layers": layers,
        "final_norm_scale": jnp.asarray(_np(sd["model.norm.weight"])),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(_np(sd["lm_head.weight"]).T)
    return params


def config_from_hf(hf_config) -> ModelConfig:
    """Map a transformers config object to a ModelConfig."""
    mt = hf_config.model_type
    if mt == "gpt_neox":
        if not getattr(hf_config, "use_parallel_residual", True):
            raise ValueError("gpt_neox with use_parallel_residual=False is not supported")
        if getattr(hf_config, "hidden_act", "gelu") != "gelu":
            raise ValueError(f"gpt_neox hidden_act={hf_config.hidden_act!r} not supported (gelu only)")
        if not getattr(hf_config, "attention_bias", True):
            raise ValueError("gpt_neox with attention_bias=False is not supported")
        if getattr(hf_config, "rope_scaling", None):
            raise ValueError("gpt_neox rope_scaling is not supported (vanilla RoPE only)")
        return ModelConfig(
            family="gpt_neox",
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_attention_heads,
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_eps=hf_config.layer_norm_eps,
            rope_theta=getattr(hf_config, "rotary_emb_base", 10000.0),
            rotary_pct=hf_config.rotary_pct,
            tie_word_embeddings=hf_config.tie_word_embeddings,
        )
    if mt == "llama":
        scaling = getattr(hf_config, "rope_scaling", None)
        rope_scaling = None
        if scaling:
            kind = scaling.get("rope_type", scaling.get("type"))
            if kind != "llama3":
                raise ValueError(f"llama rope_scaling type {kind!r} is not "
                                 f"supported (llama3 or none)")
            rope_scaling = ("llama3", float(scaling["factor"]),
                            float(scaling["low_freq_factor"]),
                            float(scaling["high_freq_factor"]),
                            int(scaling["original_max_position_embeddings"]))
        if getattr(hf_config, "attention_bias", False):
            raise ValueError("llama with attention_bias=True is not supported")
        hd = getattr(hf_config, "head_dim", None)
        if hd and hd * hf_config.num_attention_heads != hf_config.hidden_size:
            raise ValueError("llama with head_dim != hidden_size/num_heads is "
                             "not supported")
        return ModelConfig(
            family="llama",
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_key_value_heads,
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_eps=hf_config.rms_norm_eps,
            rope_theta=hf_config.rope_theta,
            tie_word_embeddings=hf_config.tie_word_embeddings,
            rope_scaling=rope_scaling,
        )
    if mt == "qwen2":
        if getattr(hf_config, "rope_scaling", None):
            raise ValueError("qwen2 rope_scaling is not supported (vanilla RoPE only)")
        if getattr(hf_config, "use_sliding_window", False):
            raise ValueError("qwen2 sliding-window attention is not supported "
                             "(use_sliding_window; the mellum family is the "
                             "one with window layers)")
        return ModelConfig(
            family="qwen2",
            vocab_size=hf_config.vocab_size,
            hidden_size=hf_config.hidden_size,
            num_layers=hf_config.num_hidden_layers,
            num_heads=hf_config.num_attention_heads,
            num_kv_heads=hf_config.num_key_value_heads,
            intermediate_size=hf_config.intermediate_size,
            max_position_embeddings=hf_config.max_position_embeddings,
            norm_eps=hf_config.rms_norm_eps,
            rope_theta=hf_config.rope_theta,
            tie_word_embeddings=hf_config.tie_word_embeddings,
        )
    if mt == "mellum":
        return _mellum_config(hf_config)
    if mt == "mistral4":
        return _mistral4_config(hf_config)
    if mt == "afmoe":
        return _afmoe_config(hf_config)
    if mt == "longcat_flash":
        return _longcat_flash_config(hf_config)
    if mt == "lfm2_moe":
        return _lfm2_moe_config(hf_config)
    if mt == "KeyeVL2":
        return _keye_vl2_config(hf_config)
    if mt == "deepseek_v32":
        return _deepseek_v32_config(hf_config)
    if mt == "dots3_note":
        return _dots3_note_config(hf_config)
    raise ValueError(f"unsupported model_type: {mt}")


#: the published names of Mellum's layer kinds -> ModelConfig's
MELLUM_LAYER_KINDS = {"full_attention": "attention",
                      "sliding_attention": "sliding_attention"}


def _mellum_config(hf_config) -> ModelConfig:
    """JetBrains Mellum 2 (``model_type`` ``mellum``). The keys mapped:
    ``head_dim`` (explicit: 32 heads of 128 over a hidden size of 2304),
    ``layer_types`` (``full_attention`` / ``sliding_attention``),
    ``sliding_window``, ``rope_parameters`` by layer kind (YaRN on the full
    layers, plain RoPE of the same theta on the sliding ones),
    ``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``;
    every ``mlp_layer_types`` entry must be ``sparse`` and ``norm_topk_prob``
    true (the renormalised top-k softmax the expert layer computes)."""
    rope = hf_config.rope_parameters
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if (full.get("rope_type") != "yarn"
            or sliding.get("rope_type", "default") != "default"
            or full["rope_theta"] != sliding["rope_theta"]):
        raise ValueError(
            "mellum rope_parameters must be yarn on full_attention and "
            "default on sliding_attention, at one rope_theta; got "
            f"{rope!r}")
    if any(t != "sparse" for t in hf_config.mlp_layer_types):
        raise ValueError("mellum with a dense mlp layer is not supported "
                         "(every mlp_layer_types entry must be 'sparse')")
    if not getattr(hf_config, "norm_topk_prob", True):
        raise ValueError("mellum with norm_topk_prob=False is not supported")
    if getattr(hf_config, "attention_bias", False):
        raise ValueError("mellum with attention_bias=True is not supported")
    return ModelConfig(
        family="mellum",
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        intermediate_size=hf_config.intermediate_size,
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(full["rope_theta"]),
        tie_word_embeddings=hf_config.tie_word_embeddings,
        rope_scaling=("yarn", float(full["factor"]),
                      int(full["original_max_position_embeddings"]),
                      float(full["beta_fast"]), float(full["beta_slow"]),
                      float(full["attention_factor"])),
        layer_types=tuple(MELLUM_LAYER_KINDS[t]
                          for t in hf_config.layer_types),
        explicit_head_dim=int(hf_config.head_dim),
        sliding_window=int(hf_config.sliding_window),
        num_experts=int(hf_config.num_experts),
        experts_per_tok=int(hf_config.num_experts_per_tok),
        expert_width=int(hf_config.moe_intermediate_size),
    )


def _mistral4_config(hf_config) -> ModelConfig:
    """Mistral Small 4 (``model_type`` ``mistral4``). The keys mapped:
    ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim`` +
    ``qk_rope_head_dim`` (= ``qk_head_dim``, the explicit head width),
    ``v_head_dim``, ``rope_parameters`` (YaRN; the cos/sin factor is
    ``mscale`` over ``mscale_all_dim``'s, the softmax ``0.1 mscale_all_dim
    ln(factor) + 1`` squared, ``llama_4_scaling_beta`` the query scale's),
    ``n_routed_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``n_shared_experts`` (a shared expert that many times as wide). Every
    layer must be a routed one (``first_k_dense_replace`` 0), routing one
    group's renormalised top-k softmax unscaled, rotary pairs interleaved."""
    rope = hf_config.rope_parameters
    if rope.get("rope_type", rope.get("type")) != "yarn":
        raise ValueError(f"mistral4 rope_parameters must be yarn, got "
                         f"{rope!r}")
    for key, want in (("first_k_dense_replace", 0), ("n_group", 1),
                      ("topk_group", 1), ("norm_topk_prob", True),
                      ("routed_scaling_factor", 1), ("rope_interleave", True),
                      ("attention_bias", False), ("mlp_bias", False),
                      ("sliding_window", None)):
        if getattr(hf_config, key, want) != want:
            raise ValueError(
                f"mistral4 with {key}={getattr(hf_config, key)!r} is not "
                f"supported (only {want!r})")
    nope, rot = hf_config.qk_nope_head_dim, hf_config.qk_rope_head_dim
    if getattr(hf_config, "qk_head_dim", nope + rot) != nope + rot:
        raise ValueError("mistral4 qk_head_dim must be qk_nope_head_dim + "
                         "qk_rope_head_dim")
    factor = float(rope["factor"])

    def mscale(m):
        return 0.1 * float(m) * math.log(factor) + 1.0 if factor > 1 else 1.0

    return ModelConfig(
        family="mistral4",
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        intermediate_size=hf_config.intermediate_size,
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(rope["rope_theta"]),
        tie_word_embeddings=hf_config.tie_word_embeddings,
        rope_scaling=("yarn", factor,
                      int(rope["original_max_position_embeddings"]),
                      float(rope["beta_fast"]), float(rope["beta_slow"]),
                      mscale(rope.get("mscale", 1))
                      / mscale(rope.get("mscale_all_dim", 0))),
        layer_types=("latent_attention",) * hf_config.num_hidden_layers,
        explicit_head_dim=int(nope + rot),
        num_experts=int(hf_config.n_routed_experts),
        experts_per_tok=int(hf_config.num_experts_per_tok),
        expert_width=int(hf_config.moe_intermediate_size),
        shared_width=int(hf_config.n_shared_experts
                         * hf_config.moe_intermediate_size),
        q_lora_rank=int(hf_config.q_lora_rank),
        kv_lora_rank=int(hf_config.kv_lora_rank),
        qk_rope_head_dim=int(rot),
        v_head_dim=int(hf_config.v_head_dim),
        softmax_mscale=mscale(rope.get("mscale_all_dim", 0)),
        query_scale_beta=float(rope.get("llama_4_scaling_beta", 0.0)),
    )


def _longcat_flash_config(hf_config) -> ModelConfig:
    """Meituan LongCat-Flash (``model_type`` ``longcat_flash``). The keys
    mapped, under the names the checkpoint publishes: ``num_layers`` (layers
    of TWO attention sublayers each), ``ffn_hidden_size`` (both dense
    SwiGLUs), ``expert_ffn_hidden_size``, ``n_routed_experts``,
    ``zero_expert_num`` (identity experts after the routed ones),
    ``moe_topk``, ``routed_scaling_factor``, ``q_lora_rank``,
    ``kv_lora_rank``, ``qk_nope_head_dim`` + ``qk_rope_head_dim`` (the
    explicit head width), ``v_head_dim``, ``mla_scale_q_lora`` /
    ``mla_scale_kv_lora`` (both, or neither). A file that repeats a key under
    the name other families use (``num_hidden_layers``, ``intermediate_size``,
    ``num_key_value_heads``) must agree with the published one. Refused by
    name: an attention other than MLA, identity experts of another type,
    biases, a rope scaling, renormalised top-k weights, one rank scale
    alone."""
    for key, want in (("attention_method", "MLA"),
                      ("zero_expert_type", "identity"),
                      ("attention_bias", False), ("router_bias", False),
                      ("rope_scaling", None), ("norm_topk_prob", False),
                      ("num_hidden_layers", hf_config.num_layers),
                      ("intermediate_size", hf_config.ffn_hidden_size),
                      ("num_key_value_heads",
                       hf_config.num_attention_heads),
                      ("mla_scale_kv_lora",
                       getattr(hf_config, "mla_scale_q_lora", False))):
        if getattr(hf_config, key, want) != want:
            raise ValueError(
                f"longcat_flash with {key}={getattr(hf_config, key)!r} is "
                f"not supported (only {want!r})")
    nope, rot = hf_config.qk_nope_head_dim, hf_config.qk_rope_head_dim
    layers = int(hf_config.num_layers)
    return ModelConfig(
        family="longcat_flash",
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_attention_heads,
        intermediate_size=int(hf_config.ffn_hidden_size),
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(hf_config.rope_theta),
        tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        layer_types=("latent_attention",) * (2 * layers),
        explicit_head_dim=int(nope + rot),
        num_experts=int(hf_config.n_routed_experts),
        experts_per_tok=int(hf_config.moe_topk),
        expert_width=int(hf_config.expert_ffn_hidden_size),
        q_lora_rank=int(hf_config.q_lora_rank),
        kv_lora_rank=int(hf_config.kv_lora_rank),
        qk_rope_head_dim=int(rot),
        v_head_dim=int(hf_config.v_head_dim),
        score_func="softmax_all",
        route_scale=float(hf_config.routed_scaling_factor),
        zero_experts=int(hf_config.zero_expert_num),
        rank_scales=bool(getattr(hf_config, "mla_scale_q_lora", False)),
    )


def _afmoe_config(hf_config) -> ModelConfig:
    """Arcee Trinity (``model_type`` ``afmoe``). The keys mapped:
    ``head_dim`` (explicit), ``layer_types`` (``full_attention`` /
    ``sliding_attention``: the sliding layers rotate by plain RoPE, the full
    ones take no positions), ``sliding_window``, ``num_dense_layers`` (the
    leading layers whose feed-forward is a SwiGLU of ``intermediate_size``),
    ``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``num_shared_experts`` (a shared expert that many times as wide; 0:
    none), ``score_func`` ``sigmoid`` with ``route_norm`` and ``route_scale``,
    ``mup_enabled`` (the embedding times ``sqrt(hidden_size)``). Refused by
    name: routing by expert groups, a rope scaling, any other score."""
    for key, want in (("n_group", 1), ("topk_group", 1),
                      ("num_expert_groups", 1), ("num_limited_groups", 1),
                      ("rope_scaling", None), ("score_func", "sigmoid"),
                      ("route_norm", True), ("attention_bias", False)):
        if getattr(hf_config, key, want) != want:
            raise ValueError(
                f"afmoe with {key}={getattr(hf_config, key)!r} is not "
                f"supported (only {want!r})")
    d = int(hf_config.hidden_size)
    return ModelConfig(
        family="afmoe",
        vocab_size=hf_config.vocab_size,
        hidden_size=d,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        intermediate_size=hf_config.intermediate_size,
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(hf_config.rope_theta),
        tie_word_embeddings=hf_config.tie_word_embeddings,
        layer_types=tuple(MELLUM_LAYER_KINDS[t]
                          for t in hf_config.layer_types),
        explicit_head_dim=int(hf_config.head_dim),
        sliding_window=int(hf_config.sliding_window),
        num_experts=int(hf_config.num_experts),
        experts_per_tok=int(hf_config.num_experts_per_tok),
        expert_width=int(hf_config.moe_intermediate_size),
        shared_width=int(hf_config.num_shared_experts
                         * hf_config.moe_intermediate_size),
        embedding_multiplier=(math.sqrt(d) if getattr(
            hf_config, "mup_enabled", False) else 1.0),
        num_dense_layers=int(hf_config.num_dense_layers),
        score_func="sigmoid",
        route_scale=float(hf_config.route_scale),
    )


#: the published names of LFM2's layer kinds -> ModelConfig's
LFM2_LAYER_KINDS = {"conv": "conv", "full_attention": "attention"}


def _lfm2_moe_config(hf_config) -> ModelConfig:
    """LiquidAI LFM2 MoE (``model_type`` ``lfm2_moe``). The keys mapped:
    ``layer_types`` (``conv`` / ``full_attention``: the attention layers
    rotate by plain RoPE after a per-head norm on q and k), ``conv_L_cache``
    (the short convolution's taps), ``norm_eps``, ``num_dense_layers`` (the
    leading layers whose feed-forward is a SwiGLU of ``intermediate_size``),
    ``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
    ``routed_scaling_factor``; sigmoid scores, ``use_expert_bias`` (the
    selection bias) and ``norm_topk_prob`` (weights normalised over the
    chosen, ``+ 1e-6``); the head is tied unless the file says otherwise.
    Refused by name: a bias on the convolution or its projections, a router
    without the selection bias, unnormalised top-k weights, a rope scaling."""
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("norm_topk_prob", True), ("rope_scaling", None)):
        if getattr(hf_config, key, want) != want:
            raise ValueError(
                f"lfm2_moe with {key}={getattr(hf_config, key)!r} is not "
                f"supported (only {want!r})")
    return ModelConfig(
        family="lfm2_moe",
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        intermediate_size=hf_config.intermediate_size,
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.norm_eps,
        rope_theta=float(hf_config.rope_theta),
        tie_word_embeddings=getattr(hf_config, "tie_word_embeddings", True),
        layer_types=tuple(LFM2_LAYER_KINDS[t] for t in hf_config.layer_types),
        num_experts=int(hf_config.num_experts),
        experts_per_tok=int(hf_config.num_experts_per_tok),
        expert_width=int(hf_config.moe_intermediate_size),
        num_dense_layers=int(hf_config.num_dense_layers),
        score_func="sigmoid",
        route_scale=float(hf_config.routed_scaling_factor),
        conv_window=int(hf_config.conv_L_cache),
    )


def _lfm2_moe_params(cfg: ModelConfig, sd: dict) -> dict:
    """``models/hybrid.py``'s per-kind tree from an ``lfm2_moe`` state_dict,
    all the experts held. Tensor names as the modelling code publishes them
    (from memory of it: no checkpoint can be fetched here): ``model.layers.N.``
    ``operator_norm`` / ``ffn_norm``; a conv layer's ``conv.in_proj`` (3D, D:
    B, C, x in that order), ``conv.conv`` (D, 1, L) and ``conv.out_proj``; an
    attention layer's ``self_attn.{q,k,v}_proj``, ``self_attn.out_proj``,
    ``self_attn.q_layernorm`` / ``k_layernorm``; ``feed_forward.{w1,w3,w2}``
    (gate, up, down) dense, or ``feed_forward.gate`` (the router),
    ``feed_forward.expert_bias`` and ``feed_forward.experts.M.{w1,w3,w2}``;
    ``model.embedding_norm`` is the norm ahead of the (tied) head. Refused by
    name: a tensor the model as built does not hold (a convolution bias)."""
    if cfg.experts_held or not cfg.tie_word_embeddings:
        raise ValueError("the lfm2_moe state_dict mapping holds every expert "
                         "and a tied head")
    for name in sd:
        if name.endswith(("conv.conv.bias", "in_proj.bias", "out_proj.bias")):
            raise ValueError(f"lfm2_moe with a convolution bias ({name}) is "
                             f"not supported (conv_bias false only)")
    pre = "model.layers.{i}."
    kinds = {kind: [i for i, t in enumerate(cfg.layer_types) if t == kind]
             for kind in ("conv", "attention")}

    def rows(kind, suffix, transform=lambda w: w.T):
        return jnp.asarray(np.stack([
            transform(_np(sd[pre.format(i=i) + suffix]))
            for i in kinds[kind]]))

    def ffn(i):
        ff = pre.format(i=i) + "feed_forward."
        out = {"ln2_scale": jnp.asarray(
            _np(sd[pre.format(i=i) + "ffn_norm.weight"]))}
        if i < cfg.num_dense_layers:
            names = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
            return {**out, **{k: jnp.asarray(_np(sd[ff + n + ".weight"]).T)
                              for k, n in names.items()}}
        experts = {k: jnp.asarray(np.stack([
            _np(sd[f"{ff}experts.{e}.{n}.weight"]).T
            for e in range(cfg.num_experts)]))
            for k, n in (("w_gate", "w1"), ("w_up", "w3"), ("w_down", "w2"))}
        return {**out, **experts,
                "router": jnp.asarray(_np(sd[ff + "gate.weight"]).T),
                "router_bias": jnp.asarray(_np(sd[ff + "expert_bias"]),
                                           jnp.float32)}

    return {
        "embed": jnp.asarray(_np(sd["model.embed_tokens.weight"])),
        "final_norm_scale": jnp.asarray(
            _np(sd["model.embedding_norm.weight"])),
        "conv": {
            "ln1_scale": rows("conv", "operator_norm.weight", lambda w: w),
            "w_in": rows("conv", "conv.in_proj.weight"),
            "conv_w": rows("conv", "conv.conv.weight", lambda w: w[:, 0]),
            "w_out": rows("conv", "conv.out_proj.weight"),
        },
        "attn": {
            "ln1_scale": rows("attention", "operator_norm.weight",
                              lambda w: w),
            "wq": rows("attention", "self_attn.q_proj.weight"),
            "wk": rows("attention", "self_attn.k_proj.weight"),
            "wv": rows("attention", "self_attn.v_proj.weight"),
            "wo": rows("attention", "self_attn.out_proj.weight"),
            "q_norm": rows("attention", "self_attn.q_layernorm.weight",
                           lambda w: w),
            "k_norm": rows("attention", "self_attn.k_layernorm.weight",
                           lambda w: w),
        },
        "moe": [ffn(i) for i in range(cfg.num_layers)],
    }


def _keye_vl2_config(hf_config) -> ModelConfig:
    """Kwai Keye-VL 2.0 (``model_type`` ``KeyeVL2``), the language model. The
    keys mapped: ``head_dim`` (explicit), ``rope_theta``,
    ``rope_scaling.mrope_section`` (kept and checked: it must split the
    head's ``head_dim / 2`` frequencies; text positions are equal in the
    three streams, so the program rotates by the plain table),
    ``moe_intermediate_size``, ``num_experts``, ``num_experts_per_tok``, and
    ``sa_config``'s six: ``indexer_num_heads``, ``indexer_head_dim``,
    ``topk``; ``indexer_num_kv_heads`` (1 alone: one index key a position);
    ``q_chunk_size`` / ``kv_chunk_size`` (the tiles the published prefill
    scores in, which change no sum: positive, and otherwise unused; the
    program's own block is ``flash_attention.QBLOCK`` query rows). Refused
    by name: ``use_sliding_window`` true, a ``rope_scaling`` of another type
    than ``default``, ``decoder_sparse_step`` other than 1, a non-empty
    ``mlp_only_layers`` (either would make a dense layer of
    ``intermediate_size``), ``norm_topk_prob`` false, ``attention_bias``
    true, a tied head. The vision tower's keys are not read: text alone."""
    for key, want in (("use_sliding_window", False), ("norm_topk_prob", True),
                      ("decoder_sparse_step", 1), ("attention_bias", False),
                      ("tie_word_embeddings", False)):
        if getattr(hf_config, key, want) != want:
            raise ValueError(
                f"KeyeVL2 with {key}={getattr(hf_config, key)!r} is not "
                f"supported (only {want!r})")
    if list(getattr(hf_config, "mlp_only_layers", None) or ()):
        raise ValueError(
            f"KeyeVL2 with mlp_only_layers={hf_config.mlp_only_layers!r} is "
            f"not supported (every layer routed: only [])")
    rope = dict(hf_config.rope_scaling)
    if rope.get("rope_type", rope.get("type", "default")) != "default":
        raise ValueError(f"KeyeVL2 with rope_scaling={rope!r} is not "
                         f"supported (only rope_type 'default')")
    section = tuple(int(x) for x in rope["mrope_section"])
    if 2 * sum(section) != int(hf_config.head_dim):
        raise ValueError(
            f"KeyeVL2 mrope_section {list(section)} sums to {sum(section)}, "
            f"not to head_dim / 2 = {int(hf_config.head_dim) // 2}")
    sa = dict(hf_config.sa_config)
    if int(sa["indexer_num_kv_heads"]) != 1:
        raise ValueError(
            f"KeyeVL2 with sa_config.indexer_num_kv_heads="
            f"{sa['indexer_num_kv_heads']!r} is not supported (only 1: one "
            f"index key a position)")
    for key in ("q_chunk_size", "kv_chunk_size"):
        if int(sa[key]) < 1:
            raise ValueError(f"KeyeVL2 sa_config.{key} must be >= 1, got "
                             f"{sa[key]!r}")
    return ModelConfig(
        family="keye_vl2",
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        intermediate_size=hf_config.intermediate_size,
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(hf_config.rope_theta),
        tie_word_embeddings=False,
        layer_types=("sparse_attention",) * hf_config.num_hidden_layers,
        explicit_head_dim=int(hf_config.head_dim),
        num_experts=int(hf_config.num_experts),
        experts_per_tok=int(hf_config.num_experts_per_tok),
        expert_width=int(hf_config.moe_intermediate_size),
        index_heads=int(sa["indexer_num_heads"]),
        index_head_dim=int(sa["indexer_head_dim"]),
        index_topk=int(sa["topk"]),
        mrope_section=section,
    )


def _keye_vl2_params(cfg: ModelConfig, sd: dict) -> dict:
    """``models/hybrid.py``'s per-kind tree from a ``KeyeVL2`` state_dict's
    language model, all the experts held. Tensor names ASSUMED (no checkpoint
    can be fetched here): the Qwen3-MoE lineage's for what the config shares
    with it, ``model.layers.N.`` ``input_layernorm`` /
    ``post_attention_layernorm``, ``self_attn.{q,k,v,o}_proj``,
    ``self_attn.q_norm`` / ``k_norm``, ``mlp.gate`` (the router),
    ``mlp.experts.M.{gate,up,down}_proj``, ``model.norm``, ``lm_head``; the
    indexer's after the DeepSeek sparse-attention lineage,
    ``self_attn.indexer.{wq,wk}`` (no query bottleneck: the config has no
    rank for one), ``self_attn.indexer.k_norm`` (``weight`` and ``bias``) and
    ``self_attn.indexer.weights_proj``. Refused by name: a bias on a
    projection."""
    if cfg.experts_held:
        raise ValueError("the KeyeVL2 state_dict mapping holds every expert")
    for name in sd:
        if name.endswith("_proj.bias"):
            raise ValueError(f"KeyeVL2 with a projection bias ({name}) is "
                             f"not supported (attention_bias false only)")
    pre = "model.layers.{i}."
    n = cfg.num_layers

    def rows(suffix, transform=lambda w: w.T):
        return jnp.asarray(np.stack([
            transform(_np(sd[pre.format(i=i) + suffix])) for i in range(n)]))

    def keep(w):
        return w

    def ffn(i):
        ff = pre.format(i=i) + "mlp."
        experts = {k: jnp.asarray(np.stack([
            _np(sd[f"{ff}experts.{e}.{name}.weight"]).T
            for e in range(cfg.num_experts)]))
            for k, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                            ("w_down", "down_proj"))}
        return {"ln2_scale": jnp.asarray(_np(
                    sd[pre.format(i=i) + "post_attention_layernorm.weight"])),
                "router": jnp.asarray(_np(sd[ff + "gate.weight"]).T),
                **experts}

    return {
        "embed": jnp.asarray(_np(sd["model.embed_tokens.weight"])),
        "final_norm_scale": jnp.asarray(_np(sd["model.norm.weight"])),
        "lm_head": jnp.asarray(_np(sd["lm_head.weight"]).T),
        "sparse": {
            "ln1_scale": rows("input_layernorm.weight", keep),
            "wq": rows("self_attn.q_proj.weight"),
            "wk": rows("self_attn.k_proj.weight"),
            "wv": rows("self_attn.v_proj.weight"),
            "wo": rows("self_attn.o_proj.weight"),
            "q_norm": rows("self_attn.q_norm.weight", keep),
            "k_norm": rows("self_attn.k_norm.weight", keep),
            "wq_index": rows("self_attn.indexer.wq.weight"),
            "wk_index": rows("self_attn.indexer.wk.weight"),
            "index_norm_scale": rows("self_attn.indexer.k_norm.weight", keep),
            "index_norm_bias": rows("self_attn.indexer.k_norm.bias", keep),
            "w_index": rows("self_attn.indexer.weights_proj.weight"),
        },
        "moe": [ffn(i) for i in range(n)],
    }


def _deepseek_v32_config(hf_config) -> ModelConfig:
    """DeepSeek-V3.2-Exp (``model_type`` ``deepseek_v32``). The keys mapped:
    mistral4's latent-attention keys (``q_lora_rank``, ``kv_lora_rank``,
    ``qk_nope_head_dim`` + ``qk_rope_head_dim``, ``v_head_dim``), ``rope_theta``
    and ``rope_scaling`` (YaRN; the softmax scale times ``(0.1 mscale_all_dim
    ln(factor) + 1)^2``), the indexer's ``index_n_heads``, ``index_head_dim``,
    ``index_topk``, ``first_k_dense_replace`` (the leading layers whose
    feed-forward is a SwiGLU of ``intermediate_size``), ``n_routed_experts``,
    ``num_experts_per_tok``, ``moe_intermediate_size``, ``n_shared_experts``
    (1: one shared expert of that width), ``n_group`` / ``topk_group`` (the
    group-limited selection), ``routed_scaling_factor``.
    ``num_nextn_predict_layers`` is accepted and its multi-token-prediction
    module NOT built (a draft source the serving forward pass does not run):
    said once on the log. Refused by name: a ``quantization_config`` (the
    checkpoint's FP8 storage: bf16 weights only), a ``rope_scaling`` type
    other than ``yarn``, ``mscale`` != ``mscale_all_dim`` (the cos/sin would
    carry a factor), ``attention_bias``, ``n_shared_experts`` other than 1,
    ``scoring_func`` other than ``sigmoid``, ``topk_method`` other than
    ``noaux_tc``, ``moe_layer_freq`` other than 1, ``norm_topk_prob`` false,
    a tied head."""
    if getattr(hf_config, "quantization_config", None):
        raise ValueError(
            "deepseek_v32 with a quantization_config is not supported (the "
            "checkpoint's FP8 weights and index keys: bf16 only)")
    rope = dict(getattr(hf_config, "rope_scaling", None) or {})
    if rope.get("rope_type", rope.get("type")) != "yarn":
        raise ValueError(f"deepseek_v32 rope_scaling must be yarn, got "
                         f"{rope!r}")
    if float(rope.get("mscale", 1)) != float(rope.get("mscale_all_dim", 0)):
        raise ValueError(
            f"deepseek_v32 with rope_scaling mscale={rope.get('mscale')!r} "
            f"!= mscale_all_dim={rope.get('mscale_all_dim')!r} is not "
            f"supported (equal only: cos/sin carry no factor)")
    for key, want in (("attention_bias", False), ("n_shared_experts", 1),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if getattr(hf_config, key, want) != want:
            raise ValueError(
                f"deepseek_v32 with {key}={getattr(hf_config, key)!r} is "
                f"not supported (only {want!r})")
    if getattr(hf_config, "num_nextn_predict_layers", 0):
        logging.getLogger(__name__).info(
            "deepseek_v32: num_nextn_predict_layers=%s: the multi-token-"
            "prediction module is not built and its weights are not loaded",
            hf_config.num_nextn_predict_layers)
    nope, rot = hf_config.qk_nope_head_dim, hf_config.qk_rope_head_dim
    factor = float(rope["factor"])
    n = int(hf_config.num_hidden_layers)
    return ModelConfig(
        family="deepseek_v32",
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=n,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        intermediate_size=hf_config.intermediate_size,
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(hf_config.rope_theta),
        tie_word_embeddings=False,
        rope_scaling=("yarn", factor,
                      int(rope["original_max_position_embeddings"]),
                      float(rope["beta_fast"]), float(rope["beta_slow"]),
                      1.0),
        layer_types=("sparse_latent_attention",) * n,
        explicit_head_dim=int(nope + rot),
        num_experts=int(hf_config.n_routed_experts),
        experts_per_tok=int(hf_config.num_experts_per_tok),
        expert_width=int(hf_config.moe_intermediate_size),
        shared_width=int(hf_config.moe_intermediate_size),
        q_lora_rank=int(hf_config.q_lora_rank),
        kv_lora_rank=int(hf_config.kv_lora_rank),
        qk_rope_head_dim=int(rot),
        v_head_dim=int(hf_config.v_head_dim),
        softmax_mscale=(0.1 * float(rope.get("mscale_all_dim", 0))
                        * math.log(factor) + 1.0 if factor > 1 else 1.0),
        num_dense_layers=int(hf_config.first_k_dense_replace),
        score_func="sigmoid",
        route_scale=float(hf_config.routed_scaling_factor),
        index_heads=int(hf_config.index_n_heads),
        index_head_dim=int(hf_config.index_head_dim),
        index_topk=int(hf_config.index_topk),
        route_groups=int(hf_config.n_group),
        route_groups_kept=int(hf_config.topk_group),
    )


def _v3_lineage_ffn(cfg: ModelConfig, sd: dict, i: int) -> dict:
    """Layer ``i``'s ``moe`` entry from a state dict under the DeepSeek-V3
    lineage's names (the deepseek_v32 and dots3_note mappings): a leading
    dense layer's ``mlp.{gate,up,down}_proj``, or an expert layer's
    ``mlp.gate`` (``weight``, ``e_score_correction_bias``),
    ``mlp.experts.M.*`` and ``mlp.shared_experts.*``, with the layer's
    ``post_attention_layernorm``."""
    pre = f"model.layers.{i}."
    ff = pre + "mlp."

    def swiglu(prefix, names=("w_gate", "w_up", "w_down")):
        return {k: jnp.asarray(_np(sd[f"{prefix}{name}.weight"]).T)
                for k, name in zip(names, ("gate_proj", "up_proj",
                                           "down_proj"))}

    norm = {"ln2_scale": jnp.asarray(_np(
        sd[pre + "post_attention_layernorm.weight"]))}
    if i < cfg.num_dense_layers:
        return {**norm, **swiglu(ff)}
    experts = {k: jnp.asarray(np.stack([
        _np(sd[f"{ff}experts.{e}.{name}.weight"]).T
        for e in range(cfg.num_experts)]))
        for k, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                        ("w_down", "down_proj"))}
    return {**norm, **experts,
            "router": jnp.asarray(_np(sd[ff + "gate.weight"]).T),
            "router_bias": jnp.asarray(
                _np(sd[ff + "gate.e_score_correction_bias"]), jnp.float32),
            **swiglu(ff + "shared_experts.",
                     ("shared_gate", "shared_up", "shared_down"))}


def _deepseek_v32_params(cfg: ModelConfig, sd: dict) -> dict:
    """``models/hybrid.py``'s per-kind tree from a ``deepseek_v32``
    state_dict, all the experts held. Tensor names ASSUMED (no checkpoint can
    be fetched here), the DeepSeek-V3 lineage's: ``model.layers.N.``
    ``input_layernorm`` / ``post_attention_layernorm``, ``self_attn.``
    ``q_a_proj`` / ``q_a_layernorm`` / ``q_b_proj`` / ``kv_a_proj_with_mqa``
    / ``kv_a_layernorm`` / ``kv_b_proj`` / ``o_proj``, the indexer's
    ``self_attn.indexer.{wq_b,wk}``, ``self_attn.indexer.k_norm`` (``weight``
    and ``bias``) and ``self_attn.indexer.weights_proj``; a dense layer's
    ``mlp.{gate,up,down}_proj``; an expert layer's ``mlp.gate`` (``weight``,
    ``e_score_correction_bias``), ``mlp.experts.M.{gate,up,down}_proj`` and
    ``mlp.shared_experts.{gate,up,down}_proj``; ``model.norm``, ``lm_head``.
    ``kv_b_proj``'s rows are a head's K lanes then its V lanes, as
    ``mla._kvb`` reads them. Layers past ``num_hidden_layers`` (the
    multi-token-prediction module's) are not read."""
    if cfg.experts_held:
        raise ValueError("the deepseek_v32 state_dict mapping holds every "
                         "expert")
    pre = "model.layers.{i}."
    n = cfg.num_layers

    def rows(suffix, transform=lambda w: w.T):
        return jnp.asarray(np.stack([
            transform(_np(sd[pre.format(i=i) + suffix])) for i in range(n)]))

    def keep(w):
        return w

    at = "self_attn."
    return {
        "embed": jnp.asarray(_np(sd["model.embed_tokens.weight"])),
        "final_norm_scale": jnp.asarray(_np(sd["model.norm.weight"])),
        "lm_head": jnp.asarray(_np(sd["lm_head.weight"]).T),
        "sparse_latent": {
            "ln1_scale": rows("input_layernorm.weight", keep),
            "wq_a": rows(at + "q_a_proj.weight"),
            "q_norm": rows(at + "q_a_layernorm.weight", keep),
            "wq_b": rows(at + "q_b_proj.weight"),
            "wkv_a": rows(at + "kv_a_proj_with_mqa.weight"),
            "kv_norm": rows(at + "kv_a_layernorm.weight", keep),
            "wkv_b": rows(at + "kv_b_proj.weight"),
            "wo": rows(at + "o_proj.weight"),
            "wq_index": rows(at + "indexer.wq_b.weight"),
            "wk_index": rows(at + "indexer.wk.weight"),
            "index_norm_scale": rows(at + "indexer.k_norm.weight", keep),
            "index_norm_bias": rows(at + "indexer.k_norm.bias", keep),
            "w_index": rows(at + "indexer.weights_proj.weight"),
        },
        "moe": [_v3_lineage_ffn(cfg, sd, i) for i in range(n)],
    }


#: the published names of dots3_note's layer kinds -> ModelConfig's
DOTS3_LAYER_KINDS = {"full_attention": "sparse_latent_attention",
                     "sliding_attention": "sliding_latent_attention"}


def _dots3_note_config(hf_config) -> ModelConfig:
    """dots3-note-prev's language model (``model_type`` ``dots3_note``). The
    keys mapped: ``layer_types`` (``full_attention`` a sparse latent layer at
    the flat latent keys' sizes, deepseek_v32's, with its ``index_*`` keys;
    ``sliding_attention`` a window latent layer at the ``swa_*`` keys' sizes
    over ``sliding_window_size`` keys: ``ModelConfig.window_latent``),
    ``rope_theta`` / ``swa_rope_theta`` (plain RoPE, a table a kind),
    ``apply_mla_qkv_lora_rescale`` (``rank_scales``, at each kind's own
    ranks; false: off), ``attention_gate_type`` / ``swa_attention_gate_type``
    (``head_gate``), ``first_k_dense_replace``, ``n_routed_experts``,
    ``num_experts_per_tok``, ``moe_intermediate_size``, ``n_shared_experts``
    (1), ``routed_scaling_factor``; one routing group (the config has no
    ``n_group``). Refused by name: a ``quantization_config``, a non-null
    ``rope_scaling``, a gate type other than ``"headwise"`` on either kind,
    ``attention_bias``, ``n_shared_experts`` other than 1, ``scoring_func``
    other than ``sigmoid``, ``topk_method`` other than ``noaux_tc``,
    ``moe_layer_freq`` other than 1, ``norm_topk_prob`` false, a
    ``layer_types`` entry outside the two, a tied head. The vision tower, the
    audio encoder and the multi-token-prediction module are not built."""
    if getattr(hf_config, "quantization_config", None):
        raise ValueError(
            "dots3_note with a quantization_config is not supported (bf16 "
            "weights only)")
    if getattr(hf_config, "rope_scaling", None):
        raise ValueError(
            f"dots3_note with rope_scaling={hf_config.rope_scaling!r} is "
            f"not supported (null only: plain RoPE on both kinds)")
    for key, want in (("attention_gate_type", "headwise"),
                      ("swa_attention_gate_type", "headwise"),
                      ("attention_bias", False), ("n_shared_experts", 1),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("moe_layer_freq", 1),
                      ("norm_topk_prob", True),
                      ("tie_word_embeddings", False)):
        if getattr(hf_config, key, want) != want:
            raise ValueError(
                f"dots3_note with {key}={getattr(hf_config, key)!r} is not "
                f"supported (only {want!r})")
    kinds = list(hf_config.layer_types)
    unknown = sorted(set(kinds) - set(DOTS3_LAYER_KINDS))
    if unknown or len(kinds) != int(hf_config.num_hidden_layers):
        raise ValueError(
            f"dots3_note layer_types must name one of "
            f"{sorted(DOTS3_LAYER_KINDS)} for each of the "
            f"{hf_config.num_hidden_layers} layers, got {unknown or kinds!r}")
    from .configs import LatentGeometry

    nope, rot = hf_config.qk_nope_head_dim, hf_config.qk_rope_head_dim
    sliding = "sliding_attention" in kinds
    return ModelConfig(
        family="dots3_note",
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=len(kinds),
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        intermediate_size=hf_config.intermediate_size,
        max_position_embeddings=hf_config.max_position_embeddings,
        norm_eps=hf_config.rms_norm_eps,
        rope_theta=float(hf_config.rope_theta),
        tie_word_embeddings=False,
        layer_types=tuple(DOTS3_LAYER_KINDS[t] for t in kinds),
        explicit_head_dim=int(nope + rot),
        sliding_window=(int(hf_config.sliding_window_size) if sliding
                        else 0),
        num_experts=int(hf_config.n_routed_experts),
        experts_per_tok=int(hf_config.num_experts_per_tok),
        expert_width=int(hf_config.moe_intermediate_size),
        shared_width=int(hf_config.moe_intermediate_size),
        q_lora_rank=int(hf_config.q_lora_rank),
        kv_lora_rank=int(hf_config.kv_lora_rank),
        qk_rope_head_dim=int(rot),
        v_head_dim=int(hf_config.v_head_dim),
        num_dense_layers=int(hf_config.first_k_dense_replace),
        score_func="sigmoid",
        route_scale=float(hf_config.routed_scaling_factor),
        rank_scales=bool(getattr(hf_config, "apply_mla_qkv_lora_rescale",
                                 False)),
        index_heads=int(hf_config.index_n_heads),
        index_head_dim=int(hf_config.index_head_dim),
        index_topk=int(hf_config.index_topk),
        window_latent=LatentGeometry(
            num_heads=int(hf_config.swa_num_attention_heads),
            q_lora_rank=int(hf_config.swa_q_lora_rank),
            kv_lora_rank=int(hf_config.swa_kv_lora_rank),
            qk_nope_head_dim=int(hf_config.swa_qk_nope_head_dim),
            qk_rope_head_dim=int(hf_config.swa_qk_rope_head_dim),
            v_head_dim=int(hf_config.swa_v_head_dim),
            rope_theta=float(hf_config.swa_rope_theta)) if sliding else None,
        head_gate=True,
    )


#: a state dict's names that belong to what is not built: said once, skipped
DOTS3_TOWER_PREFIXES = ("vision_tower.", "audio_tower.", "audio_encoder.",
                        "visual.", "model.mtp.")


def _dots3_note_params(cfg: ModelConfig, sd: dict) -> dict:
    """``models/hybrid.py``'s per-kind tree from a ``dots3_note`` state_dict,
    all the experts held. Tensor names ASSUMED (no checkpoint can be fetched
    here), the DeepSeek-V3 lineage's as ``_deepseek_v32_params`` reads them,
    for BOTH kinds of layer (a window layer's ``self_attn`` holds the same
    names at its own sizes and no ``indexer``), plus the gate,
    ``self_attn.gate_proj`` (H, D). Tower and multi-token-prediction weights
    in the state dict (:data:`DOTS3_TOWER_PREFIXES`) are said once on the log
    and not loaded."""
    if cfg.experts_held:
        raise ValueError("the dots3_note state_dict mapping holds every "
                         "expert")
    towers = sorted({k.split(".")[0] for k in sd
                     if k.startswith(DOTS3_TOWER_PREFIXES)})
    if towers:
        logging.getLogger(__name__).info(
            "dots3_note: %s weights are in the state dict: the towers and "
            "the multi-token-prediction module are not built and their "
            "weights are not loaded", ", ".join(towers))
    pre = "model.layers.{i}."
    at = "self_attn."

    def rows(layers, suffix, transform=lambda w: w.T):
        return jnp.asarray(np.stack([
            transform(_np(sd[pre.format(i=i) + suffix])) for i in layers]))

    def keep(w):
        return w

    def latent(layers):
        return {
            "ln1_scale": rows(layers, "input_layernorm.weight", keep),
            "wq_a": rows(layers, at + "q_a_proj.weight"),
            "q_norm": rows(layers, at + "q_a_layernorm.weight", keep),
            "wq_b": rows(layers, at + "q_b_proj.weight"),
            "wkv_a": rows(layers, at + "kv_a_proj_with_mqa.weight"),
            "kv_norm": rows(layers, at + "kv_a_layernorm.weight", keep),
            "wkv_b": rows(layers, at + "kv_b_proj.weight"),
            "wo": rows(layers, at + "o_proj.weight"),
            "wg": rows(layers, at + "gate_proj.weight"),
        }

    full = [i for i, t in enumerate(cfg.layer_types)
            if t == "sparse_latent_attention"]
    sliding = [i for i, t in enumerate(cfg.layer_types)
               if t == "sliding_latent_attention"]
    out = {
        "embed": jnp.asarray(_np(sd["model.embed_tokens.weight"])),
        "final_norm_scale": jnp.asarray(_np(sd["model.norm.weight"])),
        "lm_head": jnp.asarray(_np(sd["lm_head.weight"]).T),
        "sparse_latent": {
            **latent(full),
            "wq_index": rows(full, at + "indexer.wq_b.weight"),
            "wk_index": rows(full, at + "indexer.wk.weight"),
            "index_norm_scale": rows(full, at + "indexer.k_norm.weight",
                                     keep),
            "index_norm_bias": rows(full, at + "indexer.k_norm.bias", keep),
            "w_index": rows(full, at + "indexer.weights_proj.weight"),
        },
        "moe": [_v3_lineage_ffn(cfg, sd, i) for i in range(cfg.num_layers)],
    }
    if sliding:
        out["window_latent"] = latent(sliding)
    return out
