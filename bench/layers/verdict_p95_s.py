"""95th percentile (nearest rank) of the client wall of the window's
SEARCHED 4p8 jobs; with six in a window it is the slowest.  Per-layer: it
judges nothing."""

import math

import served


def read(run):
    walls = sorted(j["client_s"] for j in served.jobs(run, "edit", "4p8"))
    return walls[math.ceil(0.95 * len(walls)) - 1] if walls else None
