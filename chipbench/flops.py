"""Operations and bytes the algorithms need, computed from shapes.

Kept with the benchmark so that no later PR can move the yardstick. All
counts are of what the mathematics requires: recomputation (remat, the
flash backward's second pass over the score tiles) is never counted, and
padding is never counted. Copied in spirit from
``utils/profiling.lm_model_flops`` (6 x matmul parameters x tokens plus
banded attention pairs), with the forward-only count added for serving.
"""

from __future__ import annotations

BF16 = 2


def layer_matmul_params(dims) -> int:
    d, f = dims.d_model, dims.d_ff
    h, hkv, dh = dims.n_heads, dims.n_kv_heads, dims.head_dim
    return d * h * dh + d * hkv * 2 * dh + h * dh * d + 2 * d * f


def head_matmul_params(dims) -> int:
    return dims.d_model * dims.vocab


def banded_pairs(t: int, window: int | None) -> int:
    """(query, key) pairs with key <= query and query - key < window."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def attn_fwd_flops(dims, pairs: int) -> int:
    """QK^T and PV over ``pairs`` score entries, all heads, one layer."""
    return 4 * dims.n_heads * dims.head_dim * pairs


def train_flops_per_step(dims, sequences: int, seq_len: int) -> int:
    """Forward + backward of one optimizer step over ``sequences`` rows of
    ``seq_len`` tokens: 6 x matmul parameters x tokens, plus attention
    (backward = 2 x forward), over all layers and the head."""
    tokens = sequences * seq_len
    n_mm = dims.n_layers * layer_matmul_params(dims) + head_matmul_params(dims)
    attn = (3 * attn_fwd_flops(dims, banded_pairs(seq_len, dims.window))
            * dims.n_layers * sequences)
    return 6 * n_mm * tokens + attn


def serve_token_flops(dims, context: int, with_head: bool) -> int:
    """Forward of ONE token that attends ``context`` keys (itself
    included), through every layer; the head only where logits are made
    (every decode token, the last token of a prompt)."""
    ctx = context if dims.window is None else min(context, dims.window)
    f = 2 * dims.n_layers * layer_matmul_params(dims)
    f += dims.n_layers * attn_fwd_flops(dims, ctx)
    if with_head:
        f += 2 * head_matmul_params(dims)
    return f


def prefill_flops(dims, start: int, n_tokens: int, last: bool) -> int:
    """Forward of prompt positions [start, start + n_tokens)."""
    w = dims.window
    def upto(n):        # sum_{p < n} min(p + 1, w)
        if w is None or n <= w:
            return n * (n + 1) // 2
        return w * (w + 1) // 2 + (n - w) * w
    pairs = upto(start + n_tokens) - upto(start)
    f = 2 * dims.n_layers * layer_matmul_params(dims) * n_tokens
    f += dims.n_layers * attn_fwd_flops(dims, pairs)
    if last:
        f += 2 * head_matmul_params(dims)
    return f


# -- kernels: (flops, bytes) of one call, from the shapes it is given -------

def flash_fwd_cost(dims, sequences: int, seq_len: int, heads: int) -> tuple:
    """The banded flash forward over [sequences, seq_len, heads, head_dim]
    (the program repeats K/V to the query head count before the call, so
    K and V are read at ``heads`` heads). Bytes: q, k, v read, o written."""
    pairs = banded_pairs(seq_len, dims.window) * sequences
    flops = 4 * heads * dims.head_dim * pairs
    nbytes = 4 * sequences * seq_len * heads * dims.head_dim * BF16
    return flops, nbytes


def flash_bwd_cost(dims, sequences: int, seq_len: int, heads: int) -> tuple:
    """The flash backward (dq and dk/dv kernels together). Needed: the
    score tile, dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q — five
    products of 2 x head_dim operations a pair; that each kernel recomputes
    the score tile and dP for itself is the implementation's, not counted.
    Bytes: q, k, v, dO read; dq, dk, dv written."""
    pairs = banded_pairs(seq_len, dims.window) * sequences
    flops = 10 * heads * dims.head_dim * pairs
    nbytes = 7 * sequences * seq_len * heads * dims.head_dim * BF16
    return flops, nbytes


def paged_decode_cost(dims, contexts) -> tuple:
    """One decode round's paged attention over all layers: each live row
    reads K and V of its context (kv heads only) and does QK^T and PV."""
    ctx = sum(c if dims.window is None else min(c, dims.window)
              for c in contexts)
    nbytes = (2 * ctx * dims.n_kv_heads * dims.head_dim * BF16
              * dims.n_layers)
    flops = 4 * dims.n_heads * dims.head_dim * ctx * dims.n_layers
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds the chip could take, which peak bounds it)."""
    tc = flops / peaks["bf16_flops"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")
