"""Model FLOPs of one training step, from the configuration's shapes.

Counts what the model needs: 2 FLOPs per multiply-add of every matrix
product in the forward pass, the causal half of each attention or
intra-chunk mLSTM score matrix, and the backward pass as twice the forward.
Recomputation under rematerialisation is not counted, so the count stays
at or below what XLA's ``cost_analysis()`` reports for the compiled step.
"""

from __future__ import annotations


def _attn(m: dict, S: int) -> float:
    d, H = m["d_model"], m["num_heads"]
    Dh = m.get("head_dim") or d // H
    qd, kvd = H * Dh, m["num_kv_heads"] * Dh
    proj = 2 * S * (d * qd + 2 * d * kvd + qd * d)
    scores = 2 * H * Dh * S * (S + 1)          # QK^T and AV, causal half
    mlp = 2 * S * 3 * d * m["d_ff"]
    return proj + scores + mlp


def _mlstm(m: dict, S: int, chunk: int) -> float:
    d, H = m["d_model"], m["num_heads"]
    up = int(m["proj_factor"] * d)
    D = up // H
    proj = 2 * S * (2 * d * up + up * d + 3 * up * up + 2 * up * H)
    c = min(chunk, S)
    intra = (S // c) * 2 * H * D * c * (c + 1)  # causal half per chunk
    inter = 0
    if S > chunk:                                # C and N read and updated
        inter = S * H * (4 * D * D + 4 * D)
    return proj + intra + inter


def _slstm(m: dict, S: int) -> float:
    d = m["d_model"]
    up = int(m["proj_factor"] * d)
    return 2 * S * (8 * d * d + 2 * d * up)


def forward_flops(m: dict, S: int, mlstm_chunk: int = 256) -> float:
    """Forward FLOPs of one sequence of ``S`` tokens."""
    pattern = m.get("block_pattern") or ["attn"]
    groups = m["num_layers"] // len(pattern)
    per_group = 0.0
    for kind in pattern:
        if kind in ("attn", "attn_local"):
            per_group += _attn(m, S)
        elif kind == "mlstm":
            per_group += _mlstm(m, S, mlstm_chunk)
        elif kind == "slstm":
            per_group += _slstm(m, S)
        else:
            raise ValueError(f"no FLOPs count for block kind {kind!r}")
    unembed = 2 * S * m["d_model"] * m["vocab_size"]
    return groups * per_group + unembed


def train_flops_per_step(m: dict, batch: int, seq_len: int,
                         mlstm_chunk: int = 256) -> float:
    return 3.0 * batch * forward_flops(m, seq_len, mlstm_chunk)
