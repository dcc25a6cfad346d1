LAYER = "serving tier"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "discover_rps"


def read(run):
    return len(run.outcomes)
