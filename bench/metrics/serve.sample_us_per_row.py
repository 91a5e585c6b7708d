"""Host time of per-row sampling in the engine's decode steps
(serve/engine.py): the summed duration of the ``serve.sample`` spans in
the trace over the sum of their ``rows``, in microseconds."""

from bench import scopes


def read(run):
    s = scopes.of_run(run)
    if not s or not s["sampled_rows"]:
        return None
    return 1e6 * s["sample_s"] / s["sampled_rows"]
