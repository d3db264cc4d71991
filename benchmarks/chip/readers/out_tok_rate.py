"""Output tokens delivered inside the window per second of window. A
request's tokens are spread evenly between its first and last chunk, and
the part inside the window counts."""


def read(ctx):
    t0, t1 = ctx["t0"], ctx["t0"] + ctx["window_s"]
    tokens = 0.0
    for r in ctx["results"]:
        if not r.ok:
            continue
        n = r.request.output_tokens
        if r.last <= r.first:
            tokens += n if t0 <= r.first <= t1 else 0
            continue
        inside = max(0.0, min(r.last, t1) - max(r.first, t0))
        tokens += n * inside / (r.last - r.first)
    return tokens / ctx["window_s"]
