"""Share of its roofline that the fused W4A4+LRC kernel
(kernels/fused_gemm.py) reaches in the step program's prefill calls: the
least time of each call, max(int8 ops / int8 peak + float ops / bf16 peak,
required bytes / HBM bandwidth) at logical shapes (bench/work.py), summed,
over the kernel's device time in the trace.  Which bound applies is in
the run's log."""


def read(run):
    calls = (run.trace_summary or {}).get("calls", {}).get("chunk")
    if not calls or calls["kernel_s"] <= 0 or calls["kernel_least_s"] <= 0:
        return None
    return 100.0 * calls["kernel_least_s"] / calls["kernel_s"]
