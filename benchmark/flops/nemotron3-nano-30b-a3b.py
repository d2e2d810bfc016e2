"""Operations and bytes of `nemotron3-nano-30b-a3b`, from shapes alone.

Two flops for a multiply-add; backward = 2x forward, so a training step is
3x the forward count; nothing recomputed is counted, no padded position is
counted, the causal layer counts only the keys a query may see, and the
routed experts count the rows they get IN EXPECTATION (tokens x chosen x
held / published: the choice is data, the expectation is the shape's).  The
counts are of the work the layer equations state, whatever implements it.
`lens` gives the true lengths of the step's rows."""

import numpy as np

BF16, F32 = 2, 4


def _dims(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, g, n, h * p


def _scan_forward(cfg):
    """The recurrence alone, a token: each head's state update (dt x B^T
    added into h) and read-out (h C), 2 x 2 x P x N."""
    h, p, _, n, _ = _dims(cfg)
    return h * 4 * p * n


def _mamba_forward(cfg):
    """A token through one Mamba-2 layer: both projections, the depthwise
    conv's taps, the recurrence."""
    d = cfg["hidden_size"]
    h, _, g, n, inner = _dims(cfg)
    conv = inner + 2 * g * n
    return (2 * d * (2 * inner + 2 * g * n + h) + 2 * inner * d
            + 2 * cfg["conv_kernel"] * conv + _scan_forward(cfg))


def _attention_forward(cfg, lens):
    """All rows through the attention layer: q and o over the query heads, k
    and v over the key/value heads, QK^T and AV over the causal pairs."""
    d, hq, hkv, dh = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
    t = lens["len"].astype(np.float64)
    proj = np.sum(t) * (2 * 2 * d * hq * dh + 2 * 2 * d * hkv * dh)
    return float(proj + np.sum(t * (t + 1) / 2) * 2 * 2 * hq * dh)


def _expected_rows(cfg, tokens):
    """(token, choice) rows that fall on the experts held here."""
    return tokens * cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["n_routed_experts_published"]


def _routed_forward(cfg, tokens):
    """The held routed experts' two products over the rows they get."""
    return _expected_rows(cfg, tokens) * 2 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _expert_layer_forward(cfg, tokens):
    d = cfg["hidden_size"]
    router = tokens * 2 * d * cfg["n_routed_experts_published"]
    shared = tokens * 2 * 2 * d * cfg["moe_shared_expert_intermediate_size"]
    return router + shared + _routed_forward(cfg, tokens)


def _layers(cfg):
    p = cfg["hybrid_override_pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def train_step_flops(cfg, lens):
    tokens = float(np.sum(lens["len"]))
    n_m, n_a, n_e = _layers(cfg)
    forward = (n_m * tokens * _mamba_forward(cfg) + n_a * _attention_forward(cfg, lens)
               + n_e * _expert_layer_forward(cfg, tokens)
               + tokens * 2 * cfg["hidden_size"] * cfg["vocab_size"])
    return 3.0 * forward


def kernels(cfg, lens):
    """name -> (flops, least bytes) of one training step's work in that
    kernel, forward and backward, over all the layers that have it.

    ssd_scan: the recurrence of the Mamba-2 layers.  Least bytes: x and y
    [H, P] and B, C [G, N] a token in bfloat16 and dt [H] in float32, once on
    the way forward and the same again with their gradients on the way back;
    not the chunked algorithm's extra products or its states.

    moe_experts: the two grouped products of the held routed experts over
    their expected rows.  Least bytes: the held weights, and the rows' inputs
    and outputs, once a pass in bfloat16, and again with their gradients."""
    tokens = float(np.sum(lens["len"]))
    n_m, _, n_e = _layers(cfg)
    h, p, g, n, _ = _dims(cfg)
    scan_pass = tokens * ((2 * h * p + 2 * g * n) * BF16 + h * F32)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts_pass = (cfg["n_routed_experts"] * 2 * d * f + _expected_rows(cfg, tokens) * 2 * d) * BF16
    return {
        "ssd_scan": (3.0 * n_m * tokens * _scan_forward(cfg), 3.0 * n_m * scan_pass),
        "moe_experts": (3.0 * n_e * _routed_forward(cfg, tokens), 3.0 * n_e * experts_pass),
    }
