"""Operations and bytes an AFMoE configuration needs, worked out from
shapes AND from what the router did, as ``counts.py`` does for the
decoders it knows: what the ALGORITHM needs, whatever implements it; a
multiply-add is 2 operations.  Sizes come from the configuration file's
keys: ``num_experts`` is the share held here, ``router_experts`` the
layer's whole width.

An expert multiplies only the tokens routed to it, so the expert
layers' part of a step is a function of the step's ASSIGNMENTS (the
program counts those that landed on held experts,
``health()["expert_assignments"]``), not of its tokens."""

WINDOW = "sliding_attention"


def layer_kinds(cfg):
    """``(window layers, full layers, dense layers, expert layers)``."""
    run = cfg.get("layers_run", range(cfg["num_hidden_layers"]))
    kinds = [cfg["layer_types"][i] for i in run]
    window = sum(1 for k in kinds if k == WINDOW)
    dense = cfg["num_dense_layers"]
    return window, len(kinds) - window, dense, len(kinds) - dense


def attention_params(cfg):
    """Weights of one attention that sit in a matmul: q, k, v, the
    gate (hidden -> heads x d) and the output projection."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * (heads + 2 * kv) * d + 2 * h * heads * d


def expert_params(cfg):
    """One expert: gate, up and down of a SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_params(cfg):
    """Matmul weights EVERY token meets, all layers: attention
    everywhere, the dense layers' MLP, and in an expert layer the
    router over the whole layer's width and the shared experts."""
    _, _, dense, experts = layer_kinds(cfg)
    h = cfg["hidden_size"]
    return (dense + experts) * attention_params(cfg) \
        + dense * 3 * h * cfg["intermediate_size"] \
        + experts * (h * cfg.get("router_experts", cfg["num_experts"])
                     + cfg["num_shared_experts"] * expert_params(cfg))


def prompt_pairs(length, window=None):
    """Pairs of a prompt of ``length`` tokens prefilled from nothing."""
    if window is None or length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def decoder_forward_flops(cfg, tokens, full_pairs, windowed_pairs,
                          sampled_positions, assignments):
    """Forward FLOPs over ``tokens`` new positions whose attention
    spans ``full_pairs`` (query, key) pairs in a full layer and
    ``windowed_pairs`` in a window layer, with the head applied at
    ``sampled_positions`` and ``assignments`` (token, held expert)
    pairs multiplied, summed over the expert layers."""
    window, full, _, _ = layer_kinds(cfg)
    f = 2 * token_params(cfg) * tokens
    f += 4 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * (full * full_pairs + window * windowed_pairs)
    f += 2 * expert_params(cfg) * assignments
    f += 2 * cfg["hidden_size"] * cfg["vocab_size"] * sampled_positions
    return f


def expert_products(cfg, assignments, active_experts, itemsize=2):
    """(ops, bytes) of the grouped products of the routed experts,
    whatever implements them: ``assignments`` rows through gate, up and
    down; the weights of the ``active_experts`` (layer, expert) pairs
    that got a row read once; a row's hidden read and written, its
    2 x width written and its width read between the two products."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return (2 * expert_params(cfg) * assignments,
            (active_experts * expert_params(cfg)
             + assignments * (2 * h + 3 * f)) * itemsize)
