"""Operations and bytes a Falcon-H1 configuration needs, worked out
from shapes, as ``counts.py`` does for the decoders it knows: what the
ALGORITHM needs, whatever implements it; a multiply-add is 2
operations.  Sizes come from the configuration file's published keys."""

STATE_BYTES = 4          # the configuration states a float32 state


def _mixer_sizes(cfg):
    return (cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
            cfg["mamba_n_groups"], cfg["mamba_d_ssm"], cfg["mamba_d_conv"])


def layer_matmul_params(cfg):
    """Weights of one block that sit in a matmul: the mixer's two
    projections, the attention's two, the gated MLP's three."""
    h = cfg["hidden_size"]
    heads, p, n, g, d_ssm, _ = _mixer_sizes(cfg)
    mixer = h * (2 * d_ssm + 2 * g * n + heads) + d_ssm * h
    qh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    attn = h * (qh + 2 * kv) * d + qh * d * h
    return mixer + attn + 3 * h * cfg["intermediate_size"]


def ssd_flops_per_position(cfg):
    """The recurrence of one layer at one position: decay, the outer
    product's multiply-add and the read-out's, 6 a state element; the
    convolution's taps beside it."""
    heads, p, n, g, d_ssm, k = _mixer_sizes(cfg)
    return 6 * heads * p * n + 2 * k * (d_ssm + 2 * g * n)


def decoder_forward_flops(cfg, tokens, context_sum, sampled_positions):
    """Forward FLOPs over ``tokens`` new positions whose attention spans
    ``context_sum`` (query, key) pairs in total, with the output head
    applied at ``sampled_positions``."""
    layers = cfg["num_hidden_layers"]
    f = (2 * layer_matmul_params(cfg) + ssd_flops_per_position(cfg)) \
        * layers * tokens
    f += layers * 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * context_sum
    f += 2 * cfg["hidden_size"] * cfg["vocab_size"] * sampled_positions
    return f


def ssd_chunk(slots, width, heads, d_head, d_state, groups, itemsize=2):
    """(ops, bytes) of ``width`` positions of the recurrence for every
    slot in ONE layer, as the step's shape has them (pad lanes are part
    of the shape): the float32 state read and written once; a lane's x,
    B, C read in the served type and its dt in float32, its y written
    in float32."""
    state = slots * heads * d_head * d_state
    io = slots * width * ((heads * d_head + 2 * groups * d_state) * itemsize
                          + heads * 4 + heads * d_head * 4)
    return 6 * state * width, 2 * state * STATE_BYTES + io


def ssm_decode(slots, heads, d_head, d_state, groups, itemsize=2):
    """(ops, bytes) of one width-1 update of every slot's state in ONE
    layer: a chunk of one position."""
    return ssd_chunk(slots, 1, heads, d_head, d_state, groups, itemsize)


def recurrent_state_bytes(cfg, slots, itemsize=2):
    """State and conv history of every layer for ``slots`` slots."""
    heads, p, n, g, d_ssm, k = _mixer_sizes(cfg)
    per = heads * p * n * STATE_BYTES + (k - 1) * (d_ssm + 2 * g * n) * itemsize
    return cfg["num_hidden_layers"] * slots * per
