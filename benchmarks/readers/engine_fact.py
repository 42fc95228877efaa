"""``engine_fact``: a number the host recorded over the whole window
(``facts[fact]``), such as a percentile of the engine's own queue-wait
records.  None when the run recorded none."""


def read(trace, facts, params, peaks):
    return facts.get(params["fact"])
