"""Host-device transfer: bytes the loader copied to the device per window
batch, in MB (1e6 B), from `GatherReport.h2d_bytes`: the padded ids,
staged rows and reuse counts of each device-tier probe."""


def read(rec):
    b = [getattr(s.report, "h2d_bytes", None) for s in rec.steps]
    if not b or None in b or not sum(b):
        return None
    return sum(b) / 1e6 / len(rec.steps)
