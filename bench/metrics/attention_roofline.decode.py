"""Share of its roofline that paged attention
(models/common.paged_gqa_attention_block) reaches in the step program's
decode calls: the least time of each call, the larger of its attention
operations (each real row's queries against its real context, all layers)
at the bf16 peak and the K/V of that context plus the queries and outputs
in bf16 at HBM bandwidth (bench/scopes.py, ``attention_work``), summed,
over the device time of the ops whose innermost named scope is
``attention`` in those calls.  The padded gather of every row's whole
block table counts as waste."""

from bench import scopes


def read(run):
    return scopes.attention_share(run, "decode")
