"""Model FLOP utilisation of the traced window: the counter operations
of the real tiles counted (onboard and recounted on the ground, from
the reports the entry returned; operations per tile from the
benchmark's conv arithmetic) over window x chips x the bf16 peak. At
default precision the MXU takes float32 operands in bfloat16 passes,
so the bf16 peak is the one that bounds it."""


def read(run):
    t = run["trace"]
    if run["flops"] <= 0 or t.window_s <= 0:
        return None
    return 100.0 * run["flops"] / (t.window_s * run["chips"]
                                   * run["peaks"]["bf16_flops"])
