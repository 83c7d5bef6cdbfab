"""Share of the document scorings that the expensive tower's document
cache served: 1 − (corpus rows the tower computed) / (Σ D_calls), both
over every request sent, from the ramp's start until the last
of them resolved. Warm-up requests resolve before the ramp starts."""


def read(ctx):
    calls = sum(r.result.stats.D_calls for r in ctx["records"]
                if r.result is not None)
    if not calls:
        return None
    return 100.0 * (1.0 - ctx["towers_all"]["expensive"].doc_rows / calls)
