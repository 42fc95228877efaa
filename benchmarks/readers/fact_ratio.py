"""``fact_ratio``: 100 x ``num`` / ``den`` of one record of sums the
kind kept (``facts[fact]``), in percent: the share of what a program
was launched over that held live work.  None when the run kept no such
record or ``den`` summed to nothing."""


def read(trace, facts, params, peaks):
    sums = facts.get(params["fact"])
    if not sums or not sums.get(params["den"]):
        return None
    return 100.0 * sums[params["num"]] / sums[params["den"]]
