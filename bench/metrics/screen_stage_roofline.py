"""screen_stage_roofline (engine stages): the screen work at the roofline over the own device time of the stage.screen scope."""
from bench.programtrace import stage_roofline


def read(run):
    return stage_roofline(run, "screen")
