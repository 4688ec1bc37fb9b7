"""Artifact provenance — which engine produced this JSON?

The re-anchor before PR 11 cost a round of confusion because every
committed BENCH_TPU artifact silently predated the engine it was being
compared against (PRs 9-10 changed the shape layer and the whole
dispatch model; the artifacts did not say so). Every bench / fusion /
profile artifact now carries three fields:

- ``git_sha``   — the commit the writing process ran from (best
  effort: ``git rev-parse HEAD``; RW_GIT_SHA overrides for detached
  bench children; "unknown" when neither resolves);
- ``pr_tag``    — a human-readable tag for the writing engine
  (RW_PR_TAG, default ``genN``);
- ``engine_generation`` — a MONOTONIC integer bumped whenever a PR
  changes what the numbers MEAN (dispatch model, shape layer, byte
  accounting). Every artifact carries it, so one written by an older
  generation is mechanically detectable instead of a forensic exercise.

No jax import, ever: the blackbox reader CLI stamps provenance from
plain processes.
"""

from __future__ import annotations

import os
import subprocess
from typing import Dict, Optional

__all__ = ["ENGINE_GENERATION", "git_sha", "pr_tag", "stamp"]

# Bump when a PR changes what artifact numbers mean. History:
#   9  = bucketed padded shapes (padding overhead enters every metric)
#   10 = fused device-resident barrier step (dispatch counts collapse)
#   11 = modeled-bytes roofline (hbm_bytes_touched semantics change:
#        compiled-executable model, not the host byte guess)
ENGINE_GENERATION = 11

_CACHED_SHA: Optional[str] = None


def git_sha() -> str:
    """The writing process's commit (cached; never raises)."""
    global _CACHED_SHA
    env = os.environ.get("RW_GIT_SHA")
    if env:
        return env
    if _CACHED_SHA is None:
        try:
            _CACHED_SHA = (
                subprocess.run(
                    ["git", "rev-parse", "HEAD"],
                    capture_output=True,
                    text=True,
                    timeout=5,
                    cwd=os.path.dirname(os.path.abspath(__file__)),
                ).stdout.strip()
                or "unknown"
            )
        except Exception:  # noqa: BLE001 — provenance is best effort
            _CACHED_SHA = "unknown"
    return _CACHED_SHA


def pr_tag() -> str:
    return os.environ.get("RW_PR_TAG", f"gen{ENGINE_GENERATION}")


def stamp() -> Dict:
    """The three provenance fields, ready to merge into an artifact."""
    return {
        "git_sha": git_sha(),
        "pr_tag": pr_tag(),
        "engine_generation": ENGINE_GENERATION,
    }
