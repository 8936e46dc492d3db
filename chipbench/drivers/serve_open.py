"""Open loop: independent users send requests on a bursty schedule fixed
by the traffic file, whatever the engine's state; each request is timed
from when it was due."""

from chipbench import serving


def run(run):
    return serving.run_window(run, "open")
