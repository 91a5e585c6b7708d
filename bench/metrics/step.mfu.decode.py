"""Model FLOP/s utilisation of the step program's decode calls: the
operations they require (bench/work.py, at logical shapes), each at its
unit's peak (int8 for the W4A4 GEMMs, bf16 for the low-rank term,
attention over the real context and the unembedding), over the calls'
device time in the trace."""


def read(run):
    calls = (run.trace_summary or {}).get("calls", {}).get("decode")
    if not calls or calls["device_s"] <= 0 or calls["compute_s"] <= 0:
        return None
    return 100.0 * calls["compute_s"] / calls["device_s"]
