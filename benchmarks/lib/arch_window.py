"""What a configuration file says about its WINDOW layers: `arch.py`'s
sibling for the kind it does not know (`sliding_attention` entries of
`layer_types`: attention over the last `sliding_window` positions; PR 50).
`arch.attention_layers` counts the `full_attention` layers alone, which is
what the readers of the full layers' launches want. A file of its own only
because a PR that adds a cell edits no file the benchmark has: the next
`benchmark` PR folds it into `arch.py`. Data and arithmetic only."""

from __future__ import annotations

WINDOW_KIND = "sliding_attention"


def window_layers(cfg: dict) -> int:
    return sum(1 for kind in cfg.get("layer_types") or ()
               if kind == WINDOW_KIND)
