"""``repro.runtime`` — checkpoint/runtime support for the clustering engine.

Only :mod:`.checkpoint` (bit-exact snapshot/resume, used by the serving
layer) and :mod:`.compile_cache` (the persistent compilation cache the
entry scripts turn on) are part of the product surface.  The elastic-reshard and
fault-tolerance scaffolding for the dormant LM training arc is
quarantined in :mod:`.elastic` / :mod:`.fault` — import those
explicitly; they are intentionally NOT loaded from the package front
(docs/design.md #9, mirroring ``repro.serve.lm``).
"""

from . import checkpoint, compile_cache

__all__ = ["checkpoint", "compile_cache"]
