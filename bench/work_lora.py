"""Operations a federated LoRA round on the granite-4.0-h block requires,
counted from the layer shapes of the configuration file (as
``bench/work.py`` counts the DNN's).

Counted: matmul FLOPs (2 per multiply-add) of the frozen base and the
adapters, the SSD scan's contractions in their chunked form (causal half of
each chunk), attention's causal scores and values, and the routed experts
at their expected share of the token-choices (``top_k`` of
``experts_published``, times the experts held).  Not counted: what a
client that is byzantine or blocked computes, recomputation (remat), the
dense expert share's work for tokens that did not choose an expert,
elementwise work, and the screening (under 0.1% of a round).  Bytes: the
frozen base read once by each local step's forward, once by its backward
(shared by every client under the vmap) and once by the held-out forward.
"""

from __future__ import annotations


def adapter_params(cfg: dict) -> int:
    """D: LoRA parameters on q/k/v/o and the Mamba in/out projections."""
    d, r = cfg["hidden_size"], cfg["lora"]["rank"]
    di, n, h = cfg["mamba_expand"] * d, cfg["mamba_d_state"], cfg["mamba_n_heads"]
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    mamba = r * (d + 2 * di + 2 * n + h) + r * (di + d)
    attention = r * (d + d) + 2 * r * (d + kv) + r * (d + d)
    kinds = cfg["layer_types"]
    return kinds.count("mamba") * mamba + kinds.count("attention") * attention


def base_bytes(cfg: dict) -> int:
    """The frozen base: the layers this chip holds and the tied vocabulary
    slice, in bf16 (the Mamba mixer's ``A_log``, ``dt_bias``, ``D`` in f32,
    counted twice)."""
    d, f, fs = cfg["hidden_size"], cfg["intermediate_size"], cfg["shared_intermediate_size"]
    di, n, h, cw = (cfg["mamba_expand"] * d, cfg["mamba_d_state"], cfg["mamba_n_heads"],
                    cfg["mamba_d_conv"])
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    mamba = d * (2 * di + 2 * n + h) + (cw + 1) * (di + 2 * n) + 2 * 3 * h + di * d + di
    attention = 2 * d * d + 2 * d * kv
    ffn = 2 * d + d * cfg["experts_published"] + cfg["num_local_experts"] * 3 * d * f + 3 * d * fs
    kinds = cfg["layer_types"]
    layers = kinds.count("mamba") * mamba + kinds.count("attention") * attention
    return 2 * (layers + len(kinds) * ffn + cfg["vocab_size"] * d + d)


def forward_flops(cfg: dict, seq: int) -> dict:
    """Per-token forward FLOPs by part, for sequences of ``seq`` tokens."""
    d = cfg["hidden_size"]
    di, n, h, p = (cfg["mamba_expand"] * d, cfg["mamba_d_state"], cfg["mamba_n_heads"],
                   cfg["mamba_d_head"])
    q = min(cfg["mamba_chunk_size"], seq)
    kinds = cfg["layer_types"]
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    kv = cfg["num_key_value_heads"] * d // cfg["num_attention_heads"]
    # in_proj, out_proj, the depthwise conv
    mamba_proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d + 2 * cfg["mamba_d_conv"] * (di + 2 * n)
    # C.B scores and the values over the causal half of a chunk, the chunk
    # states B x and their read-out C S
    ssd = 2 * n * q / 2 + 2 * h * p * q / 2 + 2 * n * h * p + 2 * n * h * p
    attn = 2 * d * (2 * d + 2 * kv) + 2 * 2 * d * (seq + 1) / 2
    f, fs = cfg["intermediate_size"], cfg["shared_intermediate_size"]
    routed = cfg["num_experts_per_tok"] / cfg["experts_published"] * cfg["num_local_experts"]
    moe = 2 * d * cfg["experts_published"] + routed * 3 * 2 * d * f + 3 * 2 * d * fs
    return dict(mamba=n_m * (mamba_proj + ssd), attention=n_a * attn,
                moe=(n_m + n_a) * moe, head=2 * d * cfg["vocab_size"],
                lora=2 * adapter_params(cfg))


def token_forward(cfg: dict, seq: int) -> float:
    return float(sum(forward_flops(cfg, seq).values()))


def token_train(cfg: dict, seq: int) -> float:
    """One training token: the forward, the input gradients of every layer
    but the first (the first layer's adapters need its output gradient, not
    its input's) and of the head, and the adapters' weight gradients."""
    parts = forward_flops(cfg, seq)
    layers = len(cfg["layer_types"])
    input_grads = parts["mamba"] + parts["attention"] + parts["moe"] + parts["head"]
    input_grads -= (parts["mamba"] + parts["attention"] + parts["moe"]) / layers
    return float(sum(parts.values()) + input_grads + parts["lora"])


def lora_round_work(cfg: dict, honest_live: int, steps: int, batch: int, seq: int,
                    n_test: int) -> tuple[float, float]:
    """(FLOPs, bytes) of one round: ``honest_live`` clients each taking
    ``steps`` local steps of ``batch`` sequences, and the held-out forward
    of ``n_test`` sequences."""
    flops = (honest_live * steps * batch * seq * token_train(cfg, seq)
             + n_test * seq * token_forward(cfg, seq))
    return float(flops), float((2 * steps + 1) * base_bytes(cfg))
