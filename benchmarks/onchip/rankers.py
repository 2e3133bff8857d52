"""A configuration's ranking model, found by its ``ranking`` field.

A configuration brings its ranker as ``models/<ranking>.py``, a file
that holds three functions and nothing the harness has to be told of:

- ``make_rank_params(key, cfg) -> dict``: ``params["rank"]`` in the
  program's layout, drawn from ``key`` on the device;
- ``reference_rank(params, feats, task, precision) -> (M, S)``: the plain
  reference's ranking scores of M requests' S candidates, every product
  through ``plain.mm`` at ``precision``.  ``feats`` holds ``uf`` (M,
  d_user_in), the user feature; ``hist_rows`` (M, H), the item-table rows
  of the history; ``id_rows`` and ``rows`` (M, S), the candidates'
  item-table and corpus rows; ``cate_rows`` (M, S), their category-table
  rows;
- ``rank_flops(cfg) -> int``: the ranking's operations per served user.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

HERE = Path(__file__).resolve().parent


@functools.lru_cache(maxsize=None)
def ranking_model(name: str, here: Path = HERE):
    """The module ``models/<name>.py`` under ``here``, loaded once."""
    path = here / "models" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"ranking {name!r} has no model file {path}")
    spec = importlib.util.spec_from_file_location(
        "onchip_ranking_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
