"""Share of ATTEMPTED requests that met both of the mix's limits; a failed
request misses. Nothing where the traffic file fixes no limits."""


def read(ctx):
    limits = ctx["traffic"].get("limits") or {}
    if not limits.get("ttft_ms") or not limits.get("tpot_ms"):
        return None
    results = ctx["results"]
    if not results:
        return None
    met = sum(1 for r in results if r.ok and r.ttft_ms <= limits["ttft_ms"]
              and r.tpot_ms <= limits["tpot_ms"])
    return 100.0 * met / len(results)
