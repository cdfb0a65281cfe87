"""Fixtures shared by the benchmark's CPU tests."""
from __future__ import annotations

import pytest


@pytest.fixture
def own_trace_dir(monkeypatch, tmp_path):
    """A traced run records into a directory of its own test: test files
    run in parallel workers, and a run clears its trace directory first."""
    from bench import run, spans

    path = tmp_path / ".bench_trace"
    monkeypatch.setattr(run, "TRACE_DIR", path)
    monkeypatch.setattr(spans, "TRACE_DIR", path)
    return path
