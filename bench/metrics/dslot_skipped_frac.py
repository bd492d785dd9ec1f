"""Share of the granted digit-plane passes of the up-projection that were
skipped (early termination and the weight-side MSR bound), weighted by each
finished request's tokens (``GenerateResult.skipped_frac``)."""


def read(run):
    pairs = [(r.skipped_frac, len(r.tokens)) for r in run.results
             if r.skipped_frac is not None]
    n = sum(t for _, t in pairs)
    if not n:
        return None
    return 100.0 * sum(s * t for s, t in pairs) / n
