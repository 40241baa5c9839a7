"""Reader of a scope's share of the scoring call: device self time of the
operations under the scopes the metric's file names, over that of every
operation of the scoring calls (whole executions only), in %.

The file's ``scopes`` is a list of globs over scope paths, segment by
segment: ``layer*/attn`` holds every operation whose name stack has a segment
matching ``layer*`` followed by one matching ``attn``, wherever in the stack
and whatever lies beneath (``Model/blocks_0/layer0/attn/qkv``). Scopes reach
the reduced trace only where ``xplane_pb2`` can be imported; without them, or
where no operation lies under the globs, nothing is reported — never 0."""
from __future__ import annotations

from fnmatch import fnmatchcase
from typing import List, Optional


def under(path: str, pattern: str) -> bool:
    """Whether the scope ``path`` lies at or beneath ``pattern``."""
    have, want = path.split("/"), pattern.split("/")
    return any(all(fnmatchcase(have[at + k], part)
                   for k, part in enumerate(want))
               for at in range(len(have) - len(want) + 1))


def read(ctx: dict, spec: dict) -> Optional[float]:
    trace = ctx.get("trace") or {}
    by_module = trace.get("module_scopes")
    if not by_module:
        return None
    patterns: List[str] = spec["scopes"]
    inside = whole = 0.0
    for module, scopes in by_module.items():
        if "score" not in module:
            continue
        for path, seconds in scopes.items():
            whole += seconds
            if any(under(path, pattern) for pattern in patterns):
                inside += seconds
    return 100.0 * inside / whole if inside > 0 and whole > 0 else None
