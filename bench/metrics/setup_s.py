"""Set-up: process start to the window's start, in seconds (host clock)."""


def read(rec):
    return rec.setup_s
