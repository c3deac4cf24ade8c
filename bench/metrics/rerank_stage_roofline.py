"""rerank_stage_roofline (engine stages): the rerank work at the roofline over the own device time of the stage.rerank scope."""
from bench.programtrace import stage_roofline


def read(run):
    return stage_roofline(run, "rerank")
