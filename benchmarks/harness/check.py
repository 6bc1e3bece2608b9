"""The comparison that decides ``correct``: every answer of the window
against the plain reference, each number beside its limit."""

import numpy as np


def compare(answers, reference, sort_keys, exact):
    """Numbers compared for one query over the answers to one parameter set.

    ``wrong_cells``: cells of the exact columns (keys, counts) that differ
    from the reference, a missing or extra row counting as a whole row;
    ``sum_rel_err``: the largest relative error of any other column's cell
    (inf where one is not finite); ``rel_err.<column>``: the same for each
    such column alone.  ``per_answer`` gives (wrong cells, largest error)
    of each answer.
    """
    ref = reference.sort_values(sort_keys) if sort_keys else reference
    loose = [c for c in ref.columns if c not in exact]
    numbers = {"wrong_cells": 0, "sum_rel_err": 0.0}
    numbers.update({f"rel_err.{c}": 0.0 for c in loose})
    per_answer = []
    for got in answers:
        if sort_keys and set(sort_keys) <= set(got.columns):
            got = got.sort_values(sort_keys)
        missing = [c for c in ref.columns if c not in got.columns]
        if len(got) != len(ref) or len(ref) == 0 or missing:
            wrong = max(len(ref), len(got), 1) * len(ref.columns)
            numbers["wrong_cells"] += wrong
            per_answer.append((wrong, float("inf")))
            continue
        wrong = sum(
            int(np.sum(got[c].astype(str).to_numpy()
                       != ref[c].astype(str).to_numpy()))
            for c in exact)
        err = 0.0
        for c in loose:
            g = got[c].to_numpy().astype(np.float64)
            r = ref[c].to_numpy().astype(np.float64)
            rel = np.abs(g - r) / np.maximum(np.abs(r), 1e-300)
            rel[~np.isfinite(g)] = np.inf
            numbers[f"rel_err.{c}"] = max(numbers[f"rel_err.{c}"],
                                          float(rel.max()))
            err = max(err, float(rel.max()))
        numbers["wrong_cells"] += wrong
        numbers["sum_rel_err"] = max(numbers["sum_rel_err"], err)
        per_answer.append((wrong, err))
    return numbers, per_answer


def merge(into: dict, numbers: dict) -> dict:
    """Numbers of several parameter sets as one: counts add, errors take
    their largest."""
    for key, value in numbers.items():
        if key == "wrong_cells":
            into[key] = into.get(key, 0) + value
        else:
            into[key] = max(into.get(key, 0.0), value)
    return into


def judge(numbers: dict, limits: dict) -> bool:
    """True where every number compared keeps to its limit."""
    return all(numbers[name] <= limits[name] for name in limits)
