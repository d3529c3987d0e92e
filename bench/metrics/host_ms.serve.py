"""Serving frontend: median over the window's `serve.batch` host spans (one
per micro-batch) of each span's self time, its duration less the part its
`serve.fetch` spans (the host waiting for the device's candidates) cover,
in ms."""
import scopes


def read(info):
    tr, window = scopes.scoped(info), info["window"]
    if tr is None or window is None:
        return None
    return scopes.median_ms(scopes.self_seconds(tr, "serve.batch", "serve.fetch",
                                                window))
