"""Median time of the Helper's leg, as the Leader's transport sees it:
the harness's own timer around `HelperSession.handle_wire` (decode and
decrypt the wire request, the Helper's batched evaluation, masking and
encoding of the answer). In a traced run, over the legs that ended
while the trace ran. Layer: sessions and wire. Host clock."""

import numpy as np


def read(record):
    legs = record["window"].get("helper_legs")
    traced = record.get("traced")
    if legs and traced:
        legs = [leg for leg in legs
                if traced["t_start"] <= leg[0] <= traced["t_stop"]]
    if not legs:
        return None
    return float(np.median([ms for _, ms in legs]))
