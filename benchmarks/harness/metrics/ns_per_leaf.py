"""Nanoseconds per output leaf of full-domain DPF evaluation: the
window's host-clock time, ended by the last evaluation's output being
ready on the device, over (keys evaluated x 2^log_domain)."""


def read(record):
    window = record["window"]
    if not window.get("evaluations"):
        return None
    return window["elapsed_s"] * 1e9 / (window["evaluations"] * window["leaves"])
