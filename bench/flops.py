"""Operations and bytes that each algorithm requires, counted from
shapes. One function per algorithm; none of them reads a compiler's
estimate. A multiply-add is 2 operations. Recomputation for memory
(rematerialisation) is not counted: it is the implementation's choice,
not the algorithm's work.
"""
from __future__ import annotations


def matmul_params(m: dict) -> dict:
    """Weights of each matmul of one dense block and of the head, keyed
    by name, as (d_in, d_out)."""
    d, H, KV, hd, F = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                       m["head_dim"], m["d_ff"])
    block = {"wq": (d, H * hd), "wk": (d, KV * hd), "wv": (d, KV * hd),
             "wo": (H * hd, d)}
    if m["mlp_type"] == "swiglu":
        block.update(w_gate=(d, F), w_up=(d, F), w_down=(F, d))
    else:
        block.update(w_up=(d, F), w_down=(F, d))
    return block


def param_count(m: dict) -> int:
    """All parameters: the blocks' matmuls and norms, the embedding and
    the output head."""
    d = m["d_model"]
    per_block = sum(a * b for a, b in matmul_params(m).values()) + 2 * d
    head = 0 if m["tie_embeddings"] else m["vocab_size"] * d
    return m["num_layers"] * per_block + m["vocab_size"] * d + head + d


def attention(batch: int, seq: int, heads: int, kv_heads: int,
              head_dim: int, act_bytes: int = 2) -> dict:
    """Causal softmax attention, forward and backward, for one layer.
    Forward: QKᵀ and PV over the lower triangle, 2·S²·H·D each half; the
    backward is twice the forward. Bytes: Q, K, V and the output read or
    written once each way, and their gradients."""
    fwd = 2 * batch * seq * seq * heads * head_dim
    flops = 3 * fwd
    q = batch * seq * heads * head_dim
    kv = batch * seq * kv_heads * head_dim
    # fwd: read q, k, v, write o; bwd: read q, k, v, o, do, write dq, dk, dv
    elems = (q + 2 * kv + q) + (q + 2 * kv + q + q) + (q + 2 * kv)
    return {"flops": flops, "bytes": elems * act_bytes}


def adamw(n_params: int, param_bytes: int = 4) -> dict:
    """AdamW with global-norm clipping over n parameters: one pass reads
    the gradient for its norm; the update reads parameter, gradient and
    both moments and writes parameter and moments."""
    return {"flops": 16 * n_params,
            "bytes": (1 + 4 + 3) * n_params * param_bytes}


def sketch_update(tokens: int, width: int, k: int,
                  act_bytes: int = 2, sketch_bytes: int = 4) -> dict:
    """EMA update of one node's triple from a (T, d) activation: three
    (d, T)·(T, k) contractions; reads the activation once, the three
    (T, k) projections, and reads and writes the three (d, k) sketches."""
    flops = 3 * 2 * tokens * width * k
    byts = tokens * width * act_bytes + 3 * tokens * k * sketch_bytes \
        + 2 * 3 * width * k * sketch_bytes
    return {"flops": flops, "bytes": byts}


def factored_weight_grad(tokens: int, d_in: int, d_out: int,
                         k: int) -> int:
    """grad_W = R (Lᵀ δ) from the rank-k reconstruction A~ = L Rᵀ."""
    return 2 * k * (tokens * d_out + d_in * d_out)


def train_step(m: dict, t: dict) -> dict:
    """Model operations of one training step: 6 operations per matmul
    weight per token (forward, input gradient, weight gradient), causal
    attention forward and backward, and the output head. Where the
    traffic sketches, the MLP linears' weight gradients are the rank-k
    factored products instead of the dense ones, and each sketched node
    adds its EMA update."""
    T = t["batch"] * t["seq_len"]
    k = t["k_max"]
    per_block = 0
    for name, (a, b) in matmul_params(m).items():
        sketched = t["sketch"] and name in ("w_gate", "w_up", "w_down")
        per_block += 4 * T * a * b
        per_block += factored_weight_grad(T, a, b, k) if sketched \
            else 2 * T * a * b
    att = attention(t["batch"], t["seq_len"], m["num_heads"],
                    m["num_kv_heads"], m["head_dim"])["flops"]
    sketch = 0
    if t["sketch"]:
        sketch = sketch_update(T, m["d_model"], k)["flops"] + \
            sketch_update(T, m["d_ff"], k)["flops"]
    head = 6 * T * m["d_model"] * m["vocab_size"]
    return {"flops": m["num_layers"] * (per_block + att + sketch) + head}
