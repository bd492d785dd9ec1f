"""Row use of the expert layers in the decode steps: the (token, held
expert) rows routed over the rows the expert matmuls computed, summed over
the finished requests (``GenerateResult.expert_rows``, each the pool's rows
in the steps the request took part in)."""


def read(run):
    rows = [r.expert_rows for r in run.results
            if getattr(r, "expert_rows", None) is not None]
    computed = sum(c for _, c in rows)
    if not computed:
        return None
    return 100.0 * sum(u for u, _ in rows) / computed
