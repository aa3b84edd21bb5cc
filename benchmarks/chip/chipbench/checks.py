"""The comparison that decides ``correct``.

A record holds one cell's readings of its first rounds, as the program
produced them or as the reference replays them: per point (one point,
or one per sweep grid point) the round losses, test accuracies and
uplink counts, and where the cell exposes its state, the norm of every
parameter leaf's change after round 1 (``update_norms``) and after the
last compared round (``change_norms``), and the final G_out table.

The numbers compared, each against a limit of its own from the
workload file:

* ``loss_rel`` / ``loss_abs`` — worst relative / absolute gap of a
  round's mean local loss;
* ``acc_abs`` — worst gap of a round's test accuracy;
* ``uplinks`` — summed gap of the decoded-uplink counts (exact: 0);
* ``update_rel`` / ``change_rel`` — worst leaf gap between the program's
  and the reference's norm of the change, over the larger of the
  reference leaf's norm and the median leaf's; leaves whose round-1
  change in the reference is under a thousandth of the median leaf's
  (nought to rounding) are left out;
* ``gout_abs`` — largest gap of an entry of the final G_out table;
* ``seed_upload_abs`` — largest gap of an entry of round 1's uploads
  (the devices' Mixup samples, eq. 6) from the reference's rebuild;
* ``seed_remix_abs`` / ``seed_label_errors`` — every group of the
  server's seed set (a symmetric pair, or a label cycle of N), mixed
  forward again (member k: lam x_k + (1 - lam) x_{k+1 mod N}), has to
  give back uploads: the largest gap of an entry of a pair's from the
  nearest rebuilt upload, and the count of samples whose label is not
  that upload's minor label (or the next member's not its major one),
  plus one where the set does not hold ``n_inverse`` samples per
  device.  The cycles' gap is read as ``seed_cycle_remix_abs``: the
  program inverts a cycle with one matrix product, at the configured
  precision, so on the TPU it carries bfloat16 rounding and only its
  labels are held to a limit.
"""
from __future__ import annotations

import numpy as np


class MissingOutput(Exception):
    """The program did not produce an output the comparison needs (a
    round-1 seed set, say): the run cannot be correct."""


def _worst(values) -> float:
    """The largest value; infinite where any is not finite, so that a
    NaN anywhere fails rather than passes."""
    values = np.asarray(list(values), np.float64)
    if not np.all(np.isfinite(values)):
        return float("inf")
    return float(values.max(initial=0.0))


def _leaf_gaps(prog: dict, ref: dict, keep: list) -> list:
    med = float(np.median([ref[k] for k in ref]))
    return [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep]


def kept_leaves(ref_update: dict) -> list:
    """Leaves whose reference round-1 change is not nought to rounding."""
    med = float(np.median(list(ref_update.values())))
    return [k for k, v in ref_update.items() if v >= 1e-3 * med]


def seed_numbers(seeds: dict, uploads: dict) -> dict:
    """``seed_upload_abs``, ``seed_remix_abs`` and ``seed_label_errors``
    of the program's seed set against the rebuilt uploads."""
    ref_x = uploads["x"]
    up = np.asarray(seeds["uploaded"], np.float64).reshape(len(ref_x), -1)
    out = {"seed_upload_abs": _worst(np.abs(up - ref_x).ravel())}
    x = np.asarray(seeds["train_x"], np.float64).reshape(
        -1, ref_x.shape[1])
    y = np.asarray(seeds["train_y"])
    if y.ndim == 2:
        # no symmetric pair was found: the server trains on the uploads
        # and their soft labels
        lam = uploads["lam"]
        soft = np.zeros_like(y, np.float64)
        rows = np.arange(len(y))
        soft[rows, uploads["minor"]] += lam
        soft[rows, uploads["major"]] += 1.0 - lam
        out["seed_remix_abs"] = _worst(np.abs(x - ref_x).ravel())
        out["seed_label_errors"] = float(np.sum(
            np.abs(soft - y).max(axis=1) > 1e-6))
        return out
    gaps, start = {2: [], 3: []}, 0
    lam = uploads["lam"]
    errors = int(len(x) != uploads["want"])
    for n in seeds["groups"]:
        if start + n > len(x):
            break   # the set is cut to n_inverse per device mid-group
        g = x[start:start + n]
        remix = lam * g + (1.0 - lam) * np.roll(g, -1, axis=0)
        dist = np.abs(remix[:, None, :] - ref_x[None]).max(axis=2)
        near = dist.argmin(axis=1)
        gaps[min(n, 3)] += list(dist[np.arange(n), near])
        labels = y[start:start + n]
        errors += int(np.sum(uploads["minor"][near] != labels))
        errors += int(np.sum(uploads["major"][near] !=
                             np.roll(labels, -1)))
        start += n
    out["seed_remix_abs"] = _worst(gaps[2]) if gaps[2] else float("inf")
    out["seed_cycle_remix_abs"] = _worst(gaps[3])
    out["seed_label_errors"] = float(errors)
    return out


def compare(prog: list, ref: list) -> dict:
    """``prog`` and ``ref``: one record per point, in the same order.
    Returns ``{number: value}`` for every number both records carry."""
    gaps: dict = {"loss_rel": [], "loss_abs": [], "acc_abs": [],
                  "uplinks": []}
    for p, r in zip(prog, ref, strict=True):
        lr = np.asarray(r["loss"])
        lp = np.asarray(p["loss"][:len(lr)])
        gaps["loss_rel"] += list(np.abs(lp - lr) / np.abs(lr))
        gaps["loss_abs"] += list(np.abs(lp - lr))
        ar = np.asarray(r["acc"])
        gaps["acc_abs"] += list(np.abs(np.asarray(p["acc"][:len(ar)]) - ar))
        ur = np.asarray(r["uplinks"])
        gaps["uplinks"].append(np.sum(np.abs(
            np.asarray(p["uplinks"][:len(ur)]) - ur)))
        if "update_norms" in p and "update_norms" in r:
            keep = kept_leaves(r["update_norms"])
            gaps.setdefault("update_rel", []).extend(_leaf_gaps(
                p["update_norms"], r["update_norms"], keep))
            gaps.setdefault("change_rel", []).extend(_leaf_gaps(
                p["change_norms"], r["change_norms"], keep))
        if "gout" in p and "gout" in r:
            gaps.setdefault("gout_abs", []).extend(np.abs(
                np.asarray(p["gout"]) - np.asarray(r["gout"])).ravel())
    out = {k: _worst(v) for k, v in gaps.items()}
    out["uplinks"] = float(sum(gaps["uplinks"]))
    seeded = [seed_numbers(p["seeds"], r["uploads"])
              for p, r in zip(prog, ref) if "seeds" in p and "uploads" in r]
    for k in (seeded[0] if seeded else ()):
        out[k] = max(s[k] for s in seeded)
    return out


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{number: {"value", "limit"}}``, in the order of
    ``limits``; a number the workload gives a limit but the run did not
    produce fails."""
    rows = {k: {"value": numbers.get(k, float("inf")), "limit": lim}
            for k, lim in limits.items()}
    return all(r["value"] <= r["limit"] for r in rows.values()), rows
