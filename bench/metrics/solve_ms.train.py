"""Sweep: device self time under the program's `bpmf.solve` scope (the
batched Cholesky solve-and-sample, `gibbs.sample_mvn_precision`) per sweep
of the traced window, in ms."""
import scopes


def read(info):
    return scopes.ms_per_sweep(info, "bpmf.solve")
