"""Fold-in: median of the program's `serve.foldin.plan` host spans in the
window (CSR, bucket plan, schema padding and the copies to the device of a
cold-start batch), in ms."""
import scopes


def read(info):
    tr, window = scopes.scoped(info), info["window"]
    if tr is None or window is None:
        return None
    return scopes.median_ms([b - a for a, b in
                             scopes.spans(tr, "serve.foldin.plan", window)])
