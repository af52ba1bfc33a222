"""The dropless expert layer's grouped matmuls
(``repro.kernels.grouped_matmul``: megablox's ``gmm`` and ``tgmm``, run
under jitted wrappers named ``grouped_matmul_fwd`` / ``_bwd``).

Unlike the other kernels' counts, this one is the **whole step's**: per
MoE layer, each of the expert's matrices (gate, up, down for SwiGLU) takes
three products of 2·R·d·f — the forward, the rows' gradient and the
weight's — for the R rows the layer sends to its held experts.  At set-up
(:func:`call`) R is what even routing sends, batch · seq · k · held /
router_width; a driver that reads the step's ``moe_held_rows`` counts the
rows really held with :func:`step_count`.  Padding of each group to whole
row tiles is not counted.  Bytes: each product reads or writes its R rows
on both sides (R·d and R·f) and the held experts' matrices (E·d·f), in
bfloat16.  Counting the step and not a call keeps the share unmoved by a
change that fuses or splits calls."""

MATCH = ("grouped_matmul_fwd", "grouped_matmul_bwd")


def moe_layers(cfg) -> int:
    return sum(s.mlp == "moe" for s in cfg.pattern) * cfg.n_groups


def step_count(cfg, rows: float):
    """(operations, bytes) of one step whose MoE layers hold ``rows`` rows
    in all, summed over the layers."""
    d = cfg.d_model
    f = cfg.moe_d_ff if cfg.moe_d_ff is not None else cfg.d_ff
    products = (3 if cfg.act == "silu" else 2) * 3     # a layer's
    itemsize = 2
    return (products * 2.0 * rows * d * f,
            products * itemsize * (rows * (d + f)
                                  + moe_layers(cfg) * cfg.n_experts * d * f))


def call(cfg, rc, traffic):
    layers = moe_layers(cfg)
    if rc.moe_impl != "grouped" or not layers:
        return None
    rows = (traffic["batch"] * traffic["seq"] * cfg.n_experts_per_tok
            * cfg.n_experts / cfg.router_width)
    return step_count(cfg, layers * rows)
