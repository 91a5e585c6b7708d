"""Share of its roofline that paged attention
(models/common.paged_gqa_attention_block) reaches in the step program's
prefill chunk calls: the least time of each call, the larger of its
attention operations (each real token against its causal context, all
layers) at the bf16 peak and the K/V of the context plus the queries and
outputs in bf16 at HBM bandwidth (bench/scopes.py, ``attention_work``),
summed, over the device time of the ops whose innermost named scope is
``attention`` in those calls."""

from bench import scopes


def read(run):
    return scopes.attention_share(run, "chunk")
