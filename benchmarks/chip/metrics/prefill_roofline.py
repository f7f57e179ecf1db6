"""Least time of the work prefill calls require, over the device time
inside those calls, in percent (traced window)."""


def read(run):
    return run.roofline_pct("prefill")
