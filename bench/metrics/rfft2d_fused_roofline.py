"""Share of its roofline that the real-input 2-D kernels
(``kernels/rfft2d_fused.py``: rfft2 and irfft2) reach: the least time the
chip could take for the window's r2c and c2r transforms (``work.py``, at
the peaks of ``peaks.json``) over the kernels' device time in the trace.
``bound`` says whether bytes or operations set that least time."""
import trace_reduce
import work

MARKS = ("rfft2d",)        # rfft2d_fused.N and irfft2d_fused.N


def read(ctx):
    red, done = ctx["trace"], ctx["window"]["work"]
    if red is None or "r2c" not in done:
        return None
    seconds = trace_reduce.kernel_seconds(red, MARKS)
    got = work.roofline(work.add(done["r2c"], done["c2r"]), seconds,
                        work.peaks(ctx["device_kind"]))
    if got is None:
        return None
    share, bound = got
    return {"value": 100.0 * share, "bound": bound,
            "kernel_s": seconds}
