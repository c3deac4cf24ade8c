"""aggregate_stage_roofline (engine stages): the aggregate work at the roofline over the own device time of the stage.aggregate scope."""
from bench.programtrace import stage_roofline


def read(run):
    return stage_roofline(run, "aggregate")
