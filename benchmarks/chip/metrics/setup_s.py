"""Seconds from the start of the process to the opening of the window:
loading, making the weights, compiling or loading every program, and
warming up."""


def read(rec):
    return rec["setup_s"]
