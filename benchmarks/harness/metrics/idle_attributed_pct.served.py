"""Share of the traced window's device idle time that lies under a
logged span of the program: each idle gap (xplane_scopes.py) goes to
the innermost span of the span log that holds its midpoint, once the
log is moved onto the trace's clock by the start mark's offset
(spanlog.py). The idle time by span name goes to stderr. Layer:
device. Device trace."""

import sys

import spanlog
import xplane_scopes


def read(record):
    scopes, window, clock = (
        spanlog.scopes(record), spanlog.window(record), spanlog.clock(record)
    )
    spans = spanlog.records(record)
    if scopes is None or window is None or clock is None or not spans:
        return None
    attributed, idle, by_name = xplane_scopes.attribute_idle(
        scopes.gaps(window), spans, clock["offset_ns"]
    )
    if idle <= 0:
        return None
    for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])[:15]:
        print(f"[metric] idle under {name or '(no span)'}: {ns / 1e6:.3f} ms "
              f"({100 * ns / idle:.1f}%)", file=sys.stderr, flush=True)
    return 100.0 * attributed / idle
