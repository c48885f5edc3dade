"""Operations and bytes an algorithm needs, worked out from shapes.

Every function counts what the ALGORITHM needs at the given sizes,
whatever implements it: recomputation (remat, a split backward that
forms the scores twice) is not counted.  A multiply-add is 2 operations.
Sizes come from a configuration file's published keys (HF names)."""


def _sizes(cfg):
    h = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    kv = cfg.get("num_key_value_heads", heads)
    d = cfg.get("head_dim", h // heads)
    return h, heads, kv, d, cfg["intermediate_size"], cfg["num_hidden_layers"]


def layer_matmul_params(cfg, gated):
    """Weights of one block that sit in a matmul: qkv, out, mlp."""
    h, heads, kv, d, ffn, _ = _sizes(cfg)
    attn = h * (heads + 2 * kv) * d + heads * d * h
    return attn + (3 if gated else 2) * h * ffn


def attention_flops(heads, d, queries_times_keys):
    """QK^T and PV over ``queries_times_keys`` (query, key) pairs."""
    return 4 * heads * d * queries_times_keys


def bert_train_step_flops(cfg, batch, seq, mlm_positions, vocab):
    """Model FLOPs of one forward + backward BERT MLM step (3 x the
    forward's matmuls): encoder blocks, attention over the full
    (bidirectional) square, and the MLM head on the gathered positions
    (dense h x h, then the tied decoder h x vocab)."""
    h, heads, _, d, _, layers = _sizes(cfg)
    tokens = batch * seq
    fwd = 2 * layer_matmul_params(cfg, gated=False) * layers * tokens
    fwd += layers * attention_flops(heads, d, batch * seq * seq)
    fwd += 2 * batch * mlm_positions * (h * h + h * vocab)
    return 3 * fwd


def decoder_forward_flops(cfg, tokens, context_sum, sampled_positions):
    """Forward FLOPs of a gated-MLP decoder over ``tokens`` new
    positions whose attention spans ``context_sum`` (query, key) pairs in
    total, with the output head applied at ``sampled_positions``."""
    h, heads, _, d, _, layers = _sizes(cfg)
    f = 2 * layer_matmul_params(cfg, gated=True) * layers * tokens
    f += layers * attention_flops(heads, d, context_sum)
    f += 2 * h * cfg["vocab_size"] * sampled_positions
    return f


def kv_bytes_per_token(cfg, itemsize=2):
    """K and V rows of every layer for one cached token."""
    _, _, kv, d, _, layers = _sizes(cfg)
    return 2 * layers * kv * d * itemsize


def flash_fwd(batch, heads, seq, d, itemsize=2):
    """(ops, bytes) of one attention forward: S = QK^T, O = PV; reads
    q, k, v and writes o once."""
    return (attention_flops(heads, d, batch * seq * seq),
            4 * batch * seq * heads * d * itemsize)


def flash_bwd(batch, heads, seq, d, itemsize=2):
    """(ops, bytes) of one attention backward that keeps no
    probabilities: S again, dP = dO V^T, dV = P^T dO, dQ = dS K,
    dK = dS^T Q (5 matmuls); reads q, k, v, o, do and writes dq, dk,
    dv once."""
    return (10 * heads * d * batch * seq * seq,
            8 * batch * seq * heads * d * itemsize)


def paged_decode(cfg, context_sum, rows, itemsize=2):
    """(ops, bytes) of width-1 decode attention in ONE layer over rows
    whose live contexts add up to ``context_sum`` tokens: every live K
    and V row is read once, one new K/V row per decoding row is
    written, q read and o written."""
    _, heads, kv, d, _, _ = _sizes(cfg)
    return (attention_flops(heads, d, context_sum),
            (2 * kv * d * (context_sum + rows) + 2 * heads * d * rows)
            * itemsize)


def least_seconds(ops, nbytes, peaks):
    """The roofline's least time and which side binds."""
    t_ops = ops / (peaks["tflops_bf16"] * 1e12)
    t_bytes = nbytes / (peaks["hbm_gbs"] * 1e9)
    return max(t_ops, t_bytes), ("compute" if t_ops >= t_bytes else "memory")
