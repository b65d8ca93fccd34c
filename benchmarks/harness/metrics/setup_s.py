"""Set-up time: process start to the opening of the measured window.

Loading, data and request generation, staging, warm-up and, in a run
whose compile cache is cold, compilation. Host clock."""


def read(record):
    return record["setup_s"]
