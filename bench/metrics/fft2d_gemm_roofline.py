"""Share of its roofline that the complex 2-D kernel
(``kernels/fft2d_gemm.py``, forward and inverse) reaches: the least time
the chip could take for the window's c2c transforms (``work.py``, at the
peaks of ``peaks.json``) over the kernel's device time in the trace.
``bound`` says whether bytes or operations set that least time."""
import trace_reduce
import work

MARKS = ("fft2d_gemm",)


def read(ctx):
    red, done = ctx["trace"], ctx["window"]["work"].get("c2c")
    if red is None or done is None:
        return None
    seconds = trace_reduce.kernel_seconds(red, MARKS)
    got = work.roofline(done, seconds, work.peaks(ctx["device_kind"]))
    if got is None:
        return None
    share, bound = got
    return {"value": 100.0 * share, "bound": bound,
            "kernel_s": seconds}
