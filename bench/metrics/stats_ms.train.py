"""Sweep: device self time under the program's `bpmf.stats` scope (the
per-bucket gather and rating statistics, `gibbs.bucket_stats`) per sweep of
the traced window, in ms."""
import scopes


def read(info):
    return scopes.ms_per_sweep(info, "bpmf.stats")
