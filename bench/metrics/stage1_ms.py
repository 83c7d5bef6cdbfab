"""Mean milliseconds of the program's ``serve.stage1`` spans (stage 1's
proxy search, through the host's reads of its pools) that closed inside
the traced window, each whole, from the run's trace. The span's name and
extent are those of ``EngineCounters.span_n`` / ``span_s``. A program
without the engine's spans reads nothing."""
from harness import serve_trace


def read(ctx):
    st = serve_trace.for_ctx(ctx)
    if st is None:
        return None
    done = st.closed("serve.stage1")
    if not done:
        return None
    return 1e-6 * sum(e - s for s, e in done) / len(done)
