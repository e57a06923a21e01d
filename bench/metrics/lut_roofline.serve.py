"""Summed least time of the window's Pallas calls over their summed device
time, in percent (bench/kernels.py)."""
from bench.kernels import roofline_share


def read(ctx):
    return roofline_share(ctx["summary"], ctx["peaks"])
