"""FLOPs the window's prefill and decode calls require over the traced
window's seconds at the chip's peak, in percent."""


def read(run):
    return run.mfu_pct()
