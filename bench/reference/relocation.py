"""Plain numpy model of relocation windows, and the comparison that
decides ``correct`` for the relocation cells.

The model knows only the traffic's plan and the records made from the
seed: it replays every window's block moves on an owner per key.  The
comparison holds the program to the guarantees the configuration
states:

* ``misplaced``: records held by a place other than their owner in the
  model;
* ``lost_or_duplicated``: records held by no place, plus extra copies;
* ``rows_corrupt``: held rows whose bytes differ from the record of
  their index;
* ``dist_errors``: records whose owner in the tracked distribution is
  not their owner in the model;
* ``wire_rows_error``: rows the transport reports shipping, against the
  rows the model moved.

Every limit is 0: relocation is exact.  Nothing here imports the
program.
"""
from __future__ import annotations

import numpy as np

__all__ = ["model_owner", "model_holdings", "owner_ranges", "compare",
           "bf16_rows"]


def model_owner(traffic, windows: int) -> tuple[np.ndarray, int]:
    """Owner of each key after the first ``windows`` windows of
    ``traffic`` (set-up's included), and the rows moved between places."""
    owner = np.repeat(traffic.owner.copy(), traffic.block)
    moved, done = 0, 0
    plan = traffic.warmup()
    while done < windows:
        moves = next(plan, None)
        if moves is None:
            moves = traffic.next_window()
        for start, end, src, dest in moves:
            if not (owner[start:end] == src).all():
                raise ValueError(f"window {done}: keys {start}..{end} are "
                                 f"not all at place {src}")
            owner[start:end] = dest
            if src != dest:
                moved += end - start
        done += 1
    return owner, moved


def model_holdings(owner: np.ndarray, data: np.ndarray) -> dict:
    """``place -> (rows, keys)`` as the model holds them."""
    return {int(p): (data[owner == p], np.flatnonzero(owner == p))
            for p in np.unique(owner)}


def owner_ranges(owner: np.ndarray) -> list:
    """``owner`` as ``[(start, end, place), ...]`` runs."""
    cuts = np.flatnonzero(np.diff(owner)) + 1
    starts = np.concatenate([[0], cuts])
    ends = np.concatenate([cuts, [len(owner)]])
    return [(int(s), int(e), int(owner[s])) for s, e in zip(starts, ends)]


def bf16_rows(rows: np.ndarray) -> np.ndarray:
    """``rows`` computed one precision below float32: each word rounded
    to bfloat16 (round to nearest even) and widened back."""
    u = np.ascontiguousarray(rows, np.float32).view(np.uint32)
    keep = ((u >> 16) & 1) + np.uint32(0x7FFF)
    return ((u + keep) & np.uint32(0xFFFF0000)).view(np.float32)


def compare(holdings: dict, owners: list, data: np.ndarray,
            owner: np.ndarray, moved: int, wire_rows: int) -> dict:
    """``holdings``: place -> (rows, global indices) as the program holds
    them; ``owners``: the tracked distribution as ``[(start, end,
    owner), ...]``; ``owner``: the model's owner of each key.  Returns
    ``{name: (value, limit)}``."""
    n = data.shape[0]
    held = np.zeros(n, np.int64)
    misplaced = corrupt = 0
    want = data.view(np.uint32)
    for p, (rows, idx) in holdings.items():
        idx = np.asarray(idx, np.int64)
        if not len(idx):
            continue
        inside = (idx >= 0) & (idx < n)
        corrupt += int((~inside).sum())
        idx, rows = idx[inside], np.asarray(rows)[inside]
        np.add.at(held, idx, 1)
        misplaced += int((owner[idx] != p).sum())
        got = np.ascontiguousarray(rows).view(np.uint32).reshape(len(idx), -1)
        if got.shape[1] != want.shape[1]:
            corrupt += len(idx)
            continue
        corrupt += int((got != want[idx]).any(axis=1).sum())
    lost_dup = int((held == 0).sum() + np.maximum(held - 1, 0).sum())
    tracked = np.full(n, -1, np.int64)
    for start, end, o in owners:
        tracked[max(start, 0):min(end, n)] = o
    return {
        "misplaced": (misplaced, 0),
        "lost_or_duplicated": (lost_dup, 0),
        "rows_corrupt": (corrupt, 0),
        "dist_errors": (int((tracked != owner).sum()), 0),
        "wire_rows_error": (abs(int(wire_rows) - int(moved)), 0),
    }
