"""Host staging (`core/tiers.py:DeviceStoreTier._probe_rows`): wall time
of the loader's `stage_rows` spans in the window, per window batch, in
ms.  It holds padding the ids to the bucket, copying their host rows into
the staged buffer and counting their reuse in the look-ahead window."""


def read(rec):
    d = [s for name, s in rec.spans if name == "stage_rows"]
    return 1e3 * sum(d) / len(rec.steps) if d else None
