"""Fold-in: device self time under the program's `serve.foldin` scope (the
jitted cold-start solve, statistics included) over the window's `serve.cold`
spans (one per batch with a cold-start request), in ms."""
import scopes


def read(info):
    tr, window = scopes.scoped(info), info["window"]
    if tr is None or window is None:
        return None
    cold = scopes.spans(tr, "serve.cold", window)
    secs = scopes.scope_seconds(tr, window).get("serve.foldin")
    if not cold or secs is None:
        return None
    return 1e3 * secs / len(cold)
