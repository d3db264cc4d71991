"""Device self-time of the operations under some ``jax.named_scope``s (or,
with ``scopes: ["unscoped"]``, under none of the program's seven) over
device busy time, in percent; the scope is each operation's ``tf_op``
(``lib/spans.py``). Nothing without a capture or a device plane."""

from benchmarks.chip.lib import spans


def read(ctx, scopes):
    found = spans.of(ctx)["scopes"]
    if not found:
        return None
    return 100.0 * sum(found["seconds"].get(s, 0.0)
                       for s in scopes) / found["busy_s"]
