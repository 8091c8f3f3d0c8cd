"""Device tier: share of the window's requested rows served from the HBM
cache, in %, from the loader's `GatherReport` counts."""


def read(rec):
    req = sum(s.report.n_requests for s in rec.steps)
    return 100.0 * sum(s.report.n_hbm_hits for s in rec.steps) / req \
        if req else None
