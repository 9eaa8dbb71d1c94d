"""Set-up: process start to window start (loading, building weights,
quantizing, compiling or loading compiled programs, warming up)."""


def read(rec):
    return rec["setup_s"]
