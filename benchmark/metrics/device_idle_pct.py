"""Device idle share over the traced window: 100 x (1 - busy / window),
busy being the union of the device's op intervals in the profiler trace."""


def read(ctx):
    s = ctx.trace_summary
    if s is None or not s.devices or ctx.trace_window is None:
        return None
    window_s = ctx.trace_window[1] - ctx.trace_window[0]
    return 100.0 * (1.0 - (s.busy_ns / 1e9) / window_s)
