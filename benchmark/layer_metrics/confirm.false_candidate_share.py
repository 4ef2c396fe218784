"""1 - confirmed hits / prefilter candidates over the window, summed over
rule families (`ipt_rule_family_hits_total`,
`ipt_rule_family_candidates_total`).  Layer: confirm."""


def read(ctx):
    w = ctx["window"]
    candidates = w.delta("ipt_rule_family_candidates_total")
    if candidates <= 0:
        return None
    return 100.0 * (1.0 - w.delta("ipt_rule_family_hits_total") / candidates)
