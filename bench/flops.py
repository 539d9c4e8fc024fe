"""Model FLOPs of a dense decoder LM's training step, from its sizes.

Per token, 6 N over the parameters that take part in matrix multiplications
(every projection and the LM head; the embedding lookup is no matmul, and a
tied head counts once), plus attention's 12 L n_heads d_head T, the count of
PaLM (arXiv:2204.02311, appendix B).  Recomputed work does not count.  The
vocabulary is the published one, not the program's padded table.
"""

from __future__ import annotations


def matmul_params(m) -> int:
    H, F, L = m["d_model"], m["d_ff"], m["num_layers"]
    nh, nkv, dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    attn = H * nh * dh + 2 * H * nkv * dh + nh * dh * H
    mlp = (3 if m["mlp_kind"] == "swiglu" else 2) * H * F
    return L * (attn + mlp) + H * m["vocab_size"]


def train_flops_per_token(m, seq_len: int) -> float:
    attn = 12 * m["num_layers"] * m["num_heads"] * m["head_dim"] * seq_len
    return 6.0 * matmul_params(m) + attn
