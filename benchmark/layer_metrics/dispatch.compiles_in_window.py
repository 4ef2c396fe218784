"""The window's difference of `ipt_xla_compiles_total` +
`ipt_engine_recompiles_total`; must read 0.  Layer: device dispatch."""


def read(ctx):
    w = ctx["window"]
    if w.last("ipt_xla_compiles_total") is None:
        return None
    return (w.delta_unlabelled("ipt_xla_compiles_total")
            + w.delta_unlabelled("ipt_engine_recompiles_total"))
