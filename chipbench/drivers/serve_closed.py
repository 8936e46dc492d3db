"""Closed loop: an offline batch keeps ``outstanding`` requests in the
engine at all times; the next is sent when one finishes."""

from chipbench import serving


def run(run):
    return serving.run_window(run, "closed")
