"""Driver `train_scan_lm_keywords`: `train_scan_lm`'s session, whole, for a
language-model family whose row of `train_scan_lm.KEYWORDS` is DATA: the
configuration's own `make_keywords` map, {configuration key: `make_<family>`
keyword}, a key of a nested group written `group.key`
(`linear_attn_config.num_heads`). The row is entered in that table when the
session opens, and the configuration is handed on with every such dotted path
as a flat key beside its groups, so `train_scan_lm.build_model` and the
session's `_build_program` run as they are: the family after this one brings
a configuration, a reference and no driver.
"""

from __future__ import annotations

import importlib
from typing import Dict

from benchmark.drivers import train_scan_lm
from benchmark.drivers.train_scan_lm import Session


def _at(cfg: Dict, path: str):
    for key in path.split("."):
        cfg = cfg[key]
    return cfg


def with_row(cfg: Dict) -> Dict:
    """Enter the family's row from `cfg["make_keywords"]`; -> `cfg` with the
    map's dotted paths as flat keys too."""
    renamed = dict(cfg["make_keywords"])
    train_scan_lm.KEYWORDS[cfg["family"]] = ((), renamed)
    return dict(cfg, **{path: _at(cfg, path) for path in renamed if "." in path})


def build_model(cfg: Dict):
    return train_scan_lm.build_model(with_row(cfg))


def open_session(**kw) -> Session:
    kw["cfg"] = with_row(kw["cfg"])
    ref = importlib.import_module("benchmark.reference." + kw["cfg"]["family"])
    return Session(reference=ref, **kw)
