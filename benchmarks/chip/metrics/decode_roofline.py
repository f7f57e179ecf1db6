"""Least time of the work decode calls require, over the device time
inside those calls, in percent (traced window)."""


def read(run):
    return run.roofline_pct("decode")
