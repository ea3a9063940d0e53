"""``treereplay`` for a grid over boosting dynamics: the same grower with the
trees kept and the same tree-by-tree replay, with ``eta``, ``reg_lambda``,
``gamma`` and ``min_child_weight`` read from the grid point first and
``params`` second (``treereplay`` reads ``params`` alone).  Nothing here
imports the program."""

from __future__ import annotations

from typing import Any, Dict

from . import treereplay
from .XGBoostClassifier import dynamics


def _at(grid: Dict[str, Any], params: Dict[str, Any]) -> Dict[str, Any]:
    return {**params, **dynamics(grid, params)}


def boost_trees(codes, y, w, grid: Dict[str, Any], params: Dict[str, Any],
                precision: str = "float32"):
    """``treereplay.boost_trees`` at the grid point's dynamics."""
    return treereplay.boost_trees(codes, y, w, grid, _at(grid, params),
                                  precision)


def replay(codes, y, w, trees, prior, grid: Dict[str, Any],
           params: Dict[str, Any]):
    """``treereplay.replay`` at the grid point's dynamics."""
    return treereplay.replay(codes, y, w, trees, prior, grid,
                             _at(grid, params))
