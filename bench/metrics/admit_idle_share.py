"""Share of the traced window in which the device ran no operation while
the engine staged an admission group (a ``serve.admit`` span open: the
cheap embed, stage 1, the wait for the expensive query embeds):
100 × |idle ∩ ∪ serve.admit| ÷ window, from the run's trace. A program
without the engine's spans reads nothing."""
from harness import serve_trace, spans


def read(ctx):
    st = serve_trace.for_ctx(ctx)
    if st is None or st.window_s <= 0:
        return None
    admit = st.inside("serve.admit")
    return 100.0 * spans.total_s(spans.intersect(st.idle, admit)) / st.window_s
