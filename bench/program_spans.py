"""The program's own spans and counters (``repro.runtime.spans``), as the
per-layer metrics of the S5P job read them.

Importing this module turns the program's recording on.  Only the
per-layer metrics import it, and the harness loads those in traced runs
alone, before the warm-up job; so every untraced run keeps recording off,
and a traced run records the warm-up job too.  A metric therefore reads
the last ``run.jobs_in_window`` roots named ``s5p.job``.  A program
without ``repro.runtime.spans`` records nothing, and every reader returns
None.
"""

try:
    from repro.runtime import spans
except ImportError:
    spans = None
else:
    spans.enable()

JOB = "s5p.job"


def window_jobs(run):
    """``[(root, records under it, its counts)]``, one per job of the
    window; empty when fewer roots were recorded than the window ran."""
    n = run.jobs_in_window
    if spans is None or not n:
        return []
    recs = spans.records()
    roots = [r for r in recs if r.name == JOB and r.parent is None][-n:]
    if len(roots) < n:
        return []
    return [(root, [r for r in recs if r.root == root.id and r is not root],
             spans.counters(root.id)) for root in roots]


def seconds(run, name):
    """Seconds of the spans called ``name`` over the window's jobs, or None
    when there are none."""
    durs = [r.t1 - r.t0 for _, recs, _ in window_jobs(run) for r in recs
            if r.name == name]
    return 1e-9 * sum(durs) if durs else None


def per_job(run, name):
    """:func:`seconds` per job of the window."""
    sec = seconds(run, name)
    return None if sec is None else sec / run.jobs_in_window
