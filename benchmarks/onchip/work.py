"""Operations and bytes of the served work, from shapes alone.

They count what the algorithm needs, whatever implements it (lax or
Pallas): matrix products as 2 * m * k * n operations, and the least
bytes a pass must move to and from HBM in float32.  Selections (top-k,
the merge's pops) and elementwise work count no operations.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Sequence, Tuple

import rankers

F32 = 4
I32 = 4

PEAKS_FILE = Path(__file__).with_name("peaks.json")


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict:
    """The chip's peaks; a kind missing from the table is an error."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path.name}")
    return table[device_kind]


def mlp_flops(d_in: int, dims: Sequence[int]) -> int:
    """Operations of one row through dense layers d_in -> dims."""
    total = 0
    for h in dims:
        total += 2 * d_in * h
        d_in = h
    return total


def cluster_rank(batch: int, n_clusters: int, dim: int,
                 n_top: int) -> Tuple[int, int]:
    """(operations, bytes) of scoring a batch of user vectors against the
    codebook and keeping the top ``n_top``: read the codebook and the
    users once, write the top scores and cluster ids."""
    flops = 2 * batch * n_clusters * dim
    bytes_ = (n_clusters * dim + batch * dim) * F32 \
        + batch * n_top * (F32 + I32)
    return flops, bytes_


def top_k(batch: int, n: int, n_top: int) -> Tuple[int, int]:
    """(operations, bytes) of keeping the top ``n_top`` of ``n`` scores
    per row: read the scores, write the top scores and their ids."""
    return 0, batch * n * F32 + batch * n_top * (F32 + I32)


def serve_user_flops(cfg, here: Path = rankers.HERE) -> int:
    """Model operations of serving one user for one task: the user tower,
    the cluster scores, the exact scores of the candidates, and the
    ranking of every candidate by the configuration's ranking model
    (``rank_flops`` of ``models/<cfg.ranking>.py`` under ``here``)."""
    d_user_in = cfg.user_embed_dim + cfg.item_embed_dim
    s, d = cfg.candidates_out, cfg.embed_dim
    tower = mlp_flops(d_user_in, cfg.user_tower[:-1] + (cfg.embed_dim,))
    clusters = 2 * cfg.n_clusters * d
    exact = 2 * s * d
    rank = rankers.ranking_model(cfg.ranking, here).rank_flops(cfg)
    return tower + clusters + exact + rank


def roofline_s(flops: int, bytes_: int, peak: Dict) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = bytes_ / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
