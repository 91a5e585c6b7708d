"""The engine's host time per iteration (serve/engine.py): the self time
of the ``serve.admit``, ``serve.prefill`` and ``serve.decode`` spans in
the trace, each span's duration less its nested ``*.wait`` spans (the host
blocked on the device), summed, over the count of ``serve.decode`` spans,
in milliseconds."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    if not s or not s["decode_iterations"]:
        return None
    return 1e3 * s["host_self_s"] / s["decode_iterations"]
