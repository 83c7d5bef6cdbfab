"""Share of the stage-2 drive loop's own time in which the device ran no
operation: the seconds inside ``serve.wave`` spans and outside
``serve.admit`` (an admission staged during a wave's drain counts to
``admit_idle_share``), 100 × idle in them ÷ their length, from the run's
trace. A program without the engine's spans reads nothing."""
from harness import serve_trace, spans


def read(ctx):
    st = serve_trace.for_ctx(ctx)
    if st is None:
        return None
    drive = spans.subtract(st.inside("serve.wave"), st.inside("serve.admit"))
    length = spans.total_s(drive)
    if not length:
        return None
    return 100.0 * spans.total_s(spans.intersect(st.idle, drive)) / length
