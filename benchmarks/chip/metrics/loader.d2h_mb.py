"""Host-device transfer: bytes the loader copied back from the device per
window batch, in MB (1e6 B), from `GatherReport.d2h_bytes`: the padded
gathered rows and hit mask of each device-tier probe."""


def read(rec):
    b = [getattr(s.report, "d2h_bytes", None) for s in rec.steps]
    if not b or None in b or not sum(b):
        return None
    return sum(b) / 1e6 / len(rec.steps)
