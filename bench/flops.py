"""Operations and bytes computed from shapes, kept with the benchmark.

Model FLOPs count the matrix multiplications of the forward and backward
passes (3x the forward), with causal attention counted over the keys each
query attends to; recomputation under remat does not count.  The snapshot
programs' bytes are what they must read and write for the chunk grid the
write path uses (``core/device_snapshot._grid``).
"""
from __future__ import annotations

LANES = 128
SUBLANES = 8


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix multiplication per token:
    every projection of every layer and the output head (tied or not);
    the embedding gather and the norms do no multiplication."""
    d, hd = m["d_model"], m["head_dim"]
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
    mlp = 3 * d * m["d_ff"]
    return m["n_layers"] * (attn + mlp) + m["vocab"] * d


def param_count(m: dict, norms: bool = True) -> int:
    """All parameters of a dense GQA decoder: embedding, untied head when
    present, every layer's projections, and (``norms``) its RMSNorm gains."""
    d = m["d_model"]
    n = matmul_params(m) + (0 if m["tie_embeddings"] else m["vocab"] * d)
    if norms:
        n += m["n_layers"] * 2 * d + d
    return n


def attended_keys(m: dict, seq_len: int) -> float:
    """Mean number of keys a query attends to under the causal (and
    sliding-window) mask."""
    w = m.get("window") or seq_len
    total = sum(min(i + 1, w) for i in range(seq_len))
    return total / seq_len


def model_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward plus backward FLOPs per token (3x forward; no recompute)."""
    dense = 2 * matmul_params(m)
    attn = 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * attended_keys(
        m, seq_len)
    return 3 * (dense + attn)


def chunk_grid(nbytes: int, chunk_bytes: int):
    """(n_chunks, words_per_chunk) of a leaf on the snapshot chunk grid."""
    n_chunks = max(1, -(-nbytes // chunk_bytes))
    if n_chunks == 1:
        words = nbytes // 4
        wpc = max(LANES, -(-words // LANES) * LANES)
    else:
        wpc = chunk_bytes // 4
    return n_chunks, wpc


def snapshot_program_bytes(leaf_nbytes, chunk_bytes: int) -> int:
    """HBM bytes one snapshot of these leaves makes the staging programs
    move at the least: each leaf read once, and its packed copy (the
    padded word matrix on the chunk grid) written once."""
    total = 0
    for nbytes in leaf_nbytes:
        if nbytes == 0 or nbytes % 4:
            continue            # such a leaf takes the host path
        n_chunks, wpc = chunk_grid(nbytes, chunk_bytes)
        total += nbytes + n_chunks * wpc * 4
    return total


def leaf_bytes(m: dict, moments_bytes: int = 4) -> list:
    """Bytes of every leaf of the training state: bf16 params, fp32 Adam
    moments m and v, and the int32 step count."""
    d, hd, f, v = m["d_model"], m["head_dim"], m["d_ff"], m["vocab"]
    layer = [d, d, d * m["n_heads"] * hd, d * m["n_kv_heads"] * hd,
             d * m["n_kv_heads"] * hd, m["n_heads"] * hd * d, d * f, d * f,
             f * d]
    params = [v * d, d] + [m["n_layers"] * x for x in layer]
    if not m["tie_embeddings"]:
        params.append(d * v)
    out = [2 * p for p in params]
    out += [moments_bytes * p for p in params] * 2
    out.append(4)
    return out

