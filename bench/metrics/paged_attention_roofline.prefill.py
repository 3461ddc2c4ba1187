"""Share of the roofline reached by ``paged_attention_pallas`` in the
traced window (see ``Run.attention_least_s``)."""


def read(run):
    return run.roofline("paged_attention_pallas", run.attention_least_s())
