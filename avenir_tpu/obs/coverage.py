"""Span-coverage auditor: instrumentation that can never silently rot.

Tracing is only trustworthy if every streamed job actually emits it —
an instrumentation point lost in a refactor fails no unit test (the
artifacts are unchanged) and quietly blinds the profiling the ROADMAP's
straggler/tuning work depends on. This auditor closes that hole the
same way the chunk-invariance and merge auditors close theirs: drive
every registered stream entry (analysis/manifest.stream_entries — the
REAL runner jobs over their real corpora) under a captured recorder and
assert the MANDATORY span set showed up:

- ``stream.read``  — a raw byte block left the disk (core.stream);
- ``stream.parse`` — a block became typed data (CSV chunk parse, native
  sequence/transaction encode);
- ``stream.fold``  — a sink/device fold consumed a chunk;
- ``job.finish``   — the job sealed its fold and wrote the artifact.

``tests/test_obs.py::test_every_stream_entry_emits_the_mandatory_spans``
holds this for every entry, a case each; a deliberately de-instrumented
fold (``::test_coverage_fails_deliberately_deinstrumented_fold``) must
fail it.
"""

from __future__ import annotations

import shutil
import tempfile
from collections import Counter
from typing import List, Optional, Sequence

from avenir_tpu.obs import trace

#: the span names every stream entry must emit at least once
MANDATORY_SPANS = ("stream.read", "stream.parse", "stream.fold",
                   "job.finish")


class SpanCoverageError(RuntimeError):
    """A stream entry failed to RUN under the coverage auditor (distinct
    from running fine but emitting no spans, which is a finding row)."""


def audit_entry(spec, layout_mb: Optional[float] = None) -> dict:
    """Run one stream entry under a fresh captured recorder and report
    its mandatory-span coverage row."""
    workdir = tempfile.mkdtemp(prefix=f"obs_coverage_{spec.name}_")
    try:
        ctx = spec.prepare(workdir)
        if layout_mb is None:
            # a mid-sized layout: small enough to chunk the tiny audit
            # corpus (so per-chunk spans must repeat), big enough not to
            # crawl
            layout_mb = (spec.layouts[1] if len(spec.layouts) > 1
                         else spec.layouts[0])
        with trace.capture() as rec:
            spec.run(ctx, layout_mb)
        spans = rec.spans()
    except Exception as e:
        raise SpanCoverageError(
            f"{spec.name}: stream entry failed to run under the span "
            f"auditor: {e!r}") from e
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    names = Counter(sp.name for sp in spans)
    missing = [n for n in MANDATORY_SPANS if names.get(n, 0) < 1]
    return {"kernel": spec.name,
            "layout_mb": float(layout_mb),
            "span_counts": {n: names.get(n, 0) for n in MANDATORY_SPANS},
            "total_spans": len(spans),
            "missing": missing,
            "span_coverage_validated": not missing}


def audit_span_coverage(entries: Optional[Sequence] = None) -> List[dict]:
    """Coverage rows for every registered stream entry (or the given
    subset). Callers gate on ``span_coverage_validated`` per row."""
    if entries is None:
        from avenir_tpu.analysis.manifest import stream_entries

        entries = stream_entries()
    return [audit_entry(spec) for spec in entries]
