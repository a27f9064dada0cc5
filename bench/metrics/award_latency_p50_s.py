"""Median wall seconds from admission to first award, over every job
admitted in the window (a job unawarded at the close counts its wait)."""
from harness import quantile


def read(ctx):
    lat = ctx.run.latencies()
    return quantile(lat, 0.5) if lat else None
