"""`corpora/zipf_text.py`'s corpus, unchanged, for a body generator that
picks its words by document frequency: the builder is loaded by name and
run on the configuration as it stands, and this file only adds to
`body_context` what the Boolean task classes are binned by, each term's
document frequency on this shard (from the raw posting stream the plain
reference takes, not from the program's layout) and the shard's size."""

from __future__ import annotations

import numpy as np

from plugins import load_plugin


def build(config: dict, seed: int, docs: int) -> dict:
    out = load_plugin("corpora", "zipf_text").build(config, seed, docs)
    out["body_context"] = {
        **out["body_context"],
        "docs": docs,
        "term_df": np.diff(out["reference"]["post_start"]),
    }
    return out
