"""`ipt_pad_waste_ratio` at the end of the window, as a share.  Layer:
host prep."""


def read(ctx):
    ratio = ctx["window"].last("ipt_pad_waste_ratio")
    return None if ratio is None else 100.0 * ratio
