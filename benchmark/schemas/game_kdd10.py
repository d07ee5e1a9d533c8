"""The schema ``game_kdd10``: GLMix logistic on KDD Cup 2010's Algebra I
2008-2009 tutor logs, as LIBSVM's ``kdd2010 (algebra)`` holds them: a row
is one step a student took, the label whether the first attempt was
correct, and the features one-hot indicators of the step's student, unit,
section, problem and step, of each of its knowledge components (KCs), and of
their combinations, every field its own range of ``num_features`` columns
laid end to end. Eight fields are on every row and seven more come with each
KC of the step, so rows differ in length; each row is scaled to unit length,
every value 1/sqrt(its non-zeros), and the shard is real-valued. The fixed
effect reads that shard (``global``) by TRON; one random effect a student
reads a dense shard ``re_student`` (log(1 + Problem View), log(1 + the first
KC's opportunity count), the KC count, and the intercept last), by TRON.

The generator, the work counts and the faults are below; the plain
reference and the comparison are ``benchmark/kdd10_reference.py``. None of
them imports the program; only ``dataset``, ``estimator`` and the faults
touch it. The activity curve is ``game_music``'s, the Zipf draw
``game_criteo``'s, the optimisation blocks ``game_kdd12``'s, the leaves
``game_dense``'s.
"""

from __future__ import annotations

import dataclasses

import numpy as np

import game_criteo
import game_dense
import game_kdd12
import game_music
import kdd10_reference

model_arrays = game_dense.model_arrays
check = kdd10_reference.check

TASK = "LOGISTIC_REGRESSION"
BASE = 8  # fields on every row
MIX = np.uint64(0x9E3779B97F4A7C15)


@dataclasses.dataclass
class Data:
    indices: np.ndarray  # (n, 8 + 7 * kcs_per_step_max) int32, pad == d
    values: np.ndarray  # (n, same) float32, pad 0
    num_features: int
    entity: str
    entity_ids: np.ndarray  # (n,) int32
    num_entities: int
    table: np.ndarray  # (n, 4) float32, last column 1.0
    response: np.ndarray  # (n,) float32


# -- the generator ------------------------------------------------------------

def field_offsets(conf: dict) -> tuple[dict, dict]:
    """(name -> (first column, cardinality) of every field, the row fields
    then the KC fields laid end to end, which have to fill ``num_features``
    exactly; name -> cardinality of every field and of ``opportunity``, the
    bins)."""
    fields = list(conf["fields"]) + list(conf["kc_fields"])
    cards = {f["name"]: int(f["cardinality"]) for f in fields}
    cards["opportunity"] = int(conf["opportunity_bins"])
    out, start = {}, 0
    for f in fields:
        out[f["name"]] = (start, int(f["cardinality"]))
        start += int(f["cardinality"])
    if start != int(conf["num_features"]):
        raise SystemExit(f"game_kdd10: the fields hold {start} columns and "
                         f"num_features is {conf['num_features']}")
    return out, cards


def kc_counts(n: int, mean: float, top: int) -> np.ndarray:
    """(n,) KC counts 0..top, ascending: binomial(top, mean / top) shares of
    ``n`` by largest remainder, then rows moved between the two counts
    around the mean until the KCs sum to ``n * mean`` exactly (a whole
    number at the row counts used: ``n`` a multiple of 20). No seed."""
    from math import comb
    p = mean / top
    share = np.array([comb(top, k) * p ** k * (1 - p) ** (top - k)
                      for k in range(top + 1)]) * n
    counts = np.floor(share).astype(np.int64)
    order = np.argsort(-(share - counts), kind="stable")
    counts[order[:n - int(counts.sum())]] += 1
    lo = int(np.floor(mean))
    short = int(round(n * mean)) - int(counts @ np.arange(top + 1))
    src, dst = (lo, lo + 1) if short > 0 else (lo + 1, lo)
    move = min(abs(short), int(counts[src]))
    counts[src] -= move
    counts[dst] += move
    if counts @ np.arange(top + 1) != round(n * mean):
        raise SystemExit(f"game_kdd10: {n} rows cannot hold {mean} KCs a "
                         f"step on average")
    return np.repeat(np.arange(top + 1), counts)


def make(seed: int, conf: dict) -> Data:
    rng = np.random.default_rng(int(seed))
    n, d = int(conf["num_rows"]), int(conf["num_features"])
    g = conf["assumed_generator"]
    ent = conf["entity"]
    top = int(conf["kcs_per_step_max"])
    per_kc = len(conf["kc_fields"])
    mean_kc = (float(conf["nonzeros_per_row"]) - BASE) / per_kc
    offsets, cards = field_offsets(conf)

    # the row's own draws
    student = rng.permutation(np.repeat(
        np.arange(int(ent["count"]), dtype=np.int32),
        game_music.activity_counts(n, int(ent["count"]), ent["activity"])))
    units, sections = cards["unit"], cards["section"]
    unit_of_section = (np.arange(sections) * units // sections)
    section_of_problem = rng.integers(0, sections, cards["problem"])
    problem = rng.permutation(cards["problem"])[game_criteo.zipf_ranks(
        rng, n, cards["problem"], float(g["problem_zipf_exponent"]))]
    section = section_of_problem[problem]
    unit = unit_of_section[section]
    step = rng.permutation(cards["step"])[game_criteo.zipf_ranks(
        rng, n, cards["step"], float(g["step_zipf_exponent"]))]
    kcs = rng.permutation(kc_counts(n, mean_kc, top))
    kc = game_criteo.zipf_ranks(rng, n * top, cards["kc"],
                                float(g["kc_zipf_exponent"])).reshape(n, top)
    for j in range(1, top):  # a step's KCs are distinct
        for _ in range(top):
            same = (kc[:, :j] == kc[:, j:j + 1]).any(axis=1)
            if not same.any():
                break
            kc[same, j] = (kc[same, j] + 1) % cards["kc"]
    opportunity = 1 + np.floor(np.exp(
        float(g["opportunity_log_mean"]) + float(g["opportunity_log_sd"])
        * rng.standard_normal((n, top))))
    bins = np.minimum(np.floor(np.log2(opportunity)).astype(np.int64),
                      cards["opportunity"] - 1)
    view = rng.geometric(float(g["problem_view_p"]), n)

    parts = {"student": student, "unit": unit, "section": section,
             "problem": problem, "step": step}
    width = BASE + per_kc * top
    cols = np.empty((n, width), np.int64)
    for j, f in enumerate(conf["fields"]):
        cols[:, j] = offsets[f["name"]][0] + _column(
            f, parts, cards, salt=j)
    for t in range(top):
        parts_t = dict(parts, kc=kc[:, t], opportunity=bins[:, t])
        for i, f in enumerate(conf["kc_fields"]):
            cols[:, BASE + per_kc * t + i] = offsets[f["name"]][0] + _column(
                f, parts_t, cards, salt=BASE + i)
    length = BASE + per_kc * kcs
    live = np.arange(width)[None, :] < length[:, None]
    indices = np.where(live, cols, d).astype(np.int32)
    del cols
    values = np.where(live, (1.0 / np.sqrt(length.astype(np.float32)))[
        :, None], 0.0).astype(np.float32)
    table = np.stack([
        np.log1p(view), np.where(kcs > 0, np.log1p(opportunity[:, 0]), 0.0),
        kcs.astype(np.float64), np.ones(n)], axis=1).astype(np.float32)

    # planted effects
    w = np.zeros(d + 1, np.float32)
    w[:d] = float(g["planted_fixed_sd"]) * rng.standard_normal(
        d, dtype=np.float32)
    W = float(g["planted_slope_sd"]) * rng.standard_normal(
        (int(ent["count"]), table.shape[1]))
    W[:, -1] = float(g["planted_intercept_sd"]) * rng.standard_normal(
        int(ent["count"]))
    logits = (float(g["planted_bias"])
              + (w[indices] * values).sum(axis=1, dtype=np.float64)
              + np.einsum("nd,nd->n", table, W[student]))
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float32)
    return Data(indices, values, d, ent["name"], student.astype(np.int32),
                int(ent["count"]), table, y)


def _column(f: dict, parts: dict, cards: dict, salt: int) -> np.ndarray:
    """A field's column within its own range: the part itself for a plain
    field, the product or the hash of the pair for a combination."""
    if "of" not in f:
        return parts[f["name"]].astype(np.int64)
    a, b = (parts[p] for p in f["of"])
    ca, cb = (cards[p] for p in f["of"])
    card = int(f["cardinality"])
    if card == ca * cb:
        return a.astype(np.int64) * cb + b
    with np.errstate(over="ignore"):
        h = ((a.astype(np.uint64) * np.uint64(cb) + b.astype(np.uint64)
              + np.uint64(salt + 1)) * MIX)
        h ^= h >> np.uint64(31)
        h *= MIX
    return ((h >> np.uint64(11)) % np.uint64(card)).astype(np.int64)


def shrink(conf: dict, rows: int) -> dict:
    """The rehearsal's configuration: fewer rows, and every field's
    cardinality and the students cut in their proportion (a plain field
    keeping at least four values, an exact product staying the product of
    its cut parts); the fields, the row lengths and their scaling stay, and
    ``num_features`` is what the cut fields fill."""
    f = rows / float(conf["num_rows"])
    bins = int(conf["opportunity_bins"])
    whole = {fd["name"]: int(fd["cardinality"])
             for fd in conf["fields"] + conf["kc_fields"]}
    whole["opportunity"] = bins
    cut = {"opportunity": bins}

    def one(fd):
        parts = fd.get("of")
        if parts and int(fd["cardinality"]) == whole[parts[0]] * whole[
                parts[1]]:
            c = cut[parts[0]] * cut[parts[1]]
        else:
            c = max(4, round(int(fd["cardinality"]) * f))
        cut[fd["name"]] = c
        return dict(fd, cardinality=c)
    fields = [one(fd) for fd in conf["fields"]]
    kc_fields = [one(fd) for fd in conf["kc_fields"]]
    ent = conf["entity"]
    count = cut[ent["name"]]
    return dict(
        conf, num_rows=rows, fields=fields, kc_fields=kc_fields,
        num_features=sum(fd["cardinality"] for fd in fields + kc_fields),
        rehearsal_rows_of=int(conf["num_rows"]),
        entity=dict(ent, count=count, activity=dict(
            ent["activity"],
            rows=ent["activity"]["rows"] * count / ent["count"])))


def dataset(data: Data):
    from photon_ml_tpu.data.game_data import GameDataset, SparseShard

    n = data.response.shape[0]
    return GameDataset(
        response=data.response, offsets=np.zeros(n, np.float32),
        weights=np.ones(n, np.float32),
        feature_shards={
            "global": SparseShard(data.indices, data.values,
                                  data.num_features),
            "re_" + data.entity: data.table},
        entity_ids={data.entity: data.entity_ids},
        num_entities={data.entity: data.num_entities},
        intercept_index={"re_" + data.entity: data.table.shape[1] - 1})


# -- the estimator ------------------------------------------------------------

def estimator(cell: dict, mesh, sweeps: int, ledger_dir: str,
              feature_dtype: str):
    """The object the window drives, built as ``cli/game_train.main`` builds
    it, each coordinate with the optimisation block of its own
    (``settings["optimizers"]``)."""
    from photon_ml_tpu.api.configs import (CoordinateConfiguration,
                                           FixedEffectDataConfiguration,
                                           RandomEffectDataConfiguration)
    from photon_ml_tpu.api.estimator import GameEstimator

    if cell["configuration"]["task"] != "logistic":
        raise SystemExit("game_kdd10 knows the task logistic only")
    settings = game_music._settings(cell)  # a rehearsal's cap follows its rows
    coords = {}
    for cid, c in cell["mix"]["coordinates"].items():
        if c["type"] == "fixed":
            data = FixedEffectDataConfiguration(
                c["shard"], feature_dtype=feature_dtype)
        else:
            data = RandomEffectDataConfiguration(
                random_effect_type=c["entity"],
                feature_shard_id="re_" + c["entity"],
                active_data_upper_bound=settings.get("max_samples"),
                feature_dtype=feature_dtype)
        coords[cid] = CoordinateConfiguration(
            data=data, optimization=game_kdd12._optimization(
                settings["optimizers"][cid]))
    return GameEstimator(
        task=TASK, coordinates=coords,
        update_sequence=list(cell["mix"]["update_sequence"]), mesh=mesh,
        descent_iterations=sweeps, validation_evaluators=None,
        compute_variances_at_end=False, ledger_dir=ledger_dir)


# -- the work the traced sweep needs ------------------------------------------

def _traced_fixed(ctx):
    """(evaluations, Hessian-vector products) of the fixed effect's TRON
    solve in the traced sweep, from its ``opt_iter`` rows; None where the
    program counts no products there."""
    rows = game_kdd12._traced_fixed(ctx)
    if rows is None or any(r.get("hvps") is None for r in rows):
        return None
    return int(rows[-1]["evaluations"]), sum(int(r["hvps"]) for r in rows)


def _nonzeros(ctx) -> int:
    """The shard's non-zeros: the rows times the mean, a whole number by
    construction (``kc_counts``)."""
    conf = ctx["cell"]["configuration"]
    return int(round(int(conf["num_rows"])
                     * float(conf["nonzeros_per_row"])))


def bytes_needed(kernel: str, ctx):
    """Bytes the traced sweep's solves have to move, whatever implements
    them: a pass reads a 4-byte index and a 4-byte value a non-zero, an
    evaluation of the fixed effect two passes (margins, gradient) and a
    Hessian-vector product two (X·v, Xᵀ(D·X·v)). ``fe_hvp``: the products
    over all non-zeros. ``fe_pass``: the evaluations and the products over
    all non-zeros (``game_music``'s count, at 8 B a non-zero).
    ``fe_hot`` / ``fe_cold``: the evaluations and the products over the
    non-zeros the program's layout gives that part.
    ``tron_cg``: the fixed effect's products, and each table wave's on the
    average lane over the cells its lanes' own rows fill at 4 B a cell
    (``game_music``'s count). A pass the program makes beyond these, and
    the rescoring pass, are in the seconds and not in the bytes."""
    solved = _traced_fixed(ctx)
    if solved is None:
        return None
    evals, hvps = solved
    if kernel == "fe_hvp":
        return hvps * 2 * _nonzeros(ctx) * 8
    if kernel == "fe_pass":
        return (evals + hvps) * 2 * _nonzeros(ctx) * 8
    if kernel in ("fe_hot", "fe_cold"):
        lay = game_criteo.layout(ctx)
        if lay is None:
            return None
        return (evals + hvps) * 2 * int(lay[kernel[3:] + "_entries"]) * 8
    if kernel == "tron_cg":
        waves = game_music.traced_waves(ctx)
        if waves is None:
            return None
        width = int(ctx["cell"]["configuration"]["entity"]["features"])
        return int(hvps * 2 * _nonzeros(ctx) * 8 + sum(
            r["hvp_sum"] * game_music._lane_cells(r) * width
            for r in waves) * 2 * 4)
    return None


def sweep_flops(ctx):
    """FLOPs the traced sweep needs: a multiply and an add per non-zero in
    each of the two passes of every evaluation and every product of the
    fixed effect, and in one pass to rescore; each table wave's evaluations
    and products over its lanes' own rows, two passes each, and one pass to
    rescore every row (``game_music``'s count)."""
    solved = _traced_fixed(ctx)
    waves = game_music.traced_waves(ctx)
    if solved is None or waves is None:
        return None
    conf = ctx["cell"]["configuration"]
    n, width = int(conf["num_rows"]), int(conf["entity"]["features"])
    table = sum((r["evals_sum"] + r["hvp_sum"]) * game_music._lane_cells(r)
                for r in waves) * width
    return int(2 * 2 * sum(solved) * _nonzeros(ctx) + 2 * _nonzeros(ctx)
               + 2 * 2 * table + 2 * n * width)


# -- the faults a cell of this schema can have --------------------------------

def _values_flattened():
    """Every hot column's values replaced by its first, as if the shard
    were one-valued: the float32 block built from the first value of each
    column's entries, the cold classes as they are."""
    from photon_ml_tpu.ops import hybrid_sparse as hs

    sound = hs._dense_hot

    def broken(new_col, values, k, rows_out, feature_dtype):
        first = np.zeros(k + 1, np.float32)
        cols, at = np.unique(np.minimum(new_col, k).reshape(-1),
                             return_index=True)
        first[cols] = values.astype(np.float32).reshape(-1)[at]
        flat = np.where(new_col < k, first[np.minimum(new_col, k)], 0.0)
        return sound(new_col, flat.astype(np.float32), k, rows_out,
                     feature_dtype)
    return game_dense._patched(hs, "_dense_hot", broken)


faults = {"values-flattened": _values_flattened,
          "ratio-ignored": game_music.faults["ratio-ignored"]}


# -- run.py --selfcheck -------------------------------------------------------

def _tiny() -> dict:
    """A configuration of 2,000 rows over every field of the cell's kinds."""
    fields = [{"name": "student", "cardinality": 40},
              {"name": "unit", "cardinality": 4},
              {"name": "section", "cardinality": 9},
              {"name": "problem", "cardinality": 60},
              {"name": "step", "cardinality": 300},
              {"name": "problem_step", "of": ["problem", "step"],
               "cardinality": 900},
              {"name": "student_unit", "of": ["student", "unit"],
               "cardinality": 160},
              {"name": "student_problem", "of": ["student", "problem"],
               "cardinality": 700}]
    kc_fields = [{"name": "kc", "cardinality": 30},
                 {"name": "kc_opportunity", "of": ["kc", "opportunity"],
                  "cardinality": 480},
                 {"name": "student_kc", "of": ["student", "kc"],
                  "cardinality": 1200},
                 {"name": "unit_kc", "of": ["unit", "kc"],
                  "cardinality": 120},
                 {"name": "section_kc", "of": ["section", "kc"],
                  "cardinality": 270},
                 {"name": "problem_kc", "of": ["problem", "kc"],
                  "cardinality": 500},
                 {"name": "step_kc", "of": ["step", "kc"],
                  "cardinality": 900}]
    return {"num_rows": 2000, "nonzeros_per_row": 36.35,
            "num_features": sum(f["cardinality"]
                                for f in fields + kc_fields),
            "fields": fields, "kc_fields": kc_fields,
            "opportunity_bins": 16, "kcs_per_step_max": 8,
            "entity": {"name": "student", "count": 40, "features": 4,
                       "activity": {"rows": 100000, "floor": 1,
                                    "log_sd": 1.0}},
            "assumed_generator": {
                "problem_zipf_exponent": 1.0, "step_zipf_exponent": 1.0,
                "kc_zipf_exponent": 1.0, "problem_view_p": 0.6,
                "opportunity_log_mean": 1.5, "opportunity_log_sd": 1.2,
                "planted_fixed_sd": 1.0, "planted_slope_sd": 0.1,
                "planted_intercept_sd": 1.0, "planted_bias": 2.0}}


def check_generator():
    """The mean row length is the published one exactly, every row has its
    eight fields and seven a KC, each in its own range, at unit length;
    the KCs of a step are distinct; the exact products and the hierarchy
    hold; the same seed gives the same rows."""
    conf = _tiny()
    data = make(11, conf)
    offsets, cards = field_offsets(conf)
    d = conf["num_features"]
    live = data.indices < d
    length = live.sum(axis=1)
    assert length.sum() == round(2000 * 36.35), length.sum()
    assert length.min() >= BASE and ((length - BASE) % 7 == 0).all()
    assert np.allclose((data.values ** 2).sum(axis=1), 1.0, atol=1e-6)
    assert (data.values[~live] == 0).all()
    names = [f["name"] for f in conf["fields"] + conf["kc_fields"]]
    for j in range(data.indices.shape[1]):
        name = names[j] if j < BASE else names[BASE + (j - BASE) % 7]
        lo, card = offsets[name]
        col = data.indices[live[:, j], j]
        assert (col >= lo).all() and (col < lo + card).all(), name
    kc = data.indices[:, BASE::7]
    for r in range(0, 2000, 97):
        mine = kc[r][kc[r] < d]
        assert np.unique(mine).size == mine.size  # distinct KCs
    stu = data.indices[:, 0] - offsets["student"][0]
    assert np.array_equal(stu, data.entity_ids)
    unit = data.indices[:, 1] - offsets["unit"][0]
    assert np.array_equal(data.indices[:, 6] - offsets["student_unit"][0],
                          stu * 4 + unit)
    sec = data.indices[:, 2] - offsets["section"][0]
    assert np.array_equal(unit, sec * 4 // 9)  # a section's unit is fixed
    assert np.array_equal(data.table[:, 2], (length - BASE) / 7)
    assert (data.table[:, 3] == 1).all()
    assert 0.6 < data.response.mean() < 0.95, data.response.mean()
    again = make(11, conf)
    assert np.array_equal(again.indices, data.indices)
    assert np.array_equal(again.response, data.response)
    assert not np.array_equal(make(12, conf).indices, data.indices)
    cut = shrink(dict(conf, num_rows=1000000, num_features=d), 20000)
    field_offsets(cut)  # the cut fields fill the cut width
    assert cut["fields"][6]["cardinality"] == (
        cut["fields"][0]["cardinality"] * cut["fields"][1]["cardinality"])


def check_work():
    """The work counts over a hand-made ledger: a TRON solve of 3
    evaluations and 5 products over 1,000 rows of 36.35 non-zeros, one
    table wave."""
    ctx = {"cell": {"configuration": {
        "num_rows": 1000, "nonzeros_per_row": 36.35,
        "entity": {"features": 4}}},
        "traced_sweep": 3,
        "ledger_rows": [
            {"kind": "fe_layout", "hot_entries": 20000,
             "cold_entries": 16350},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 0, "hvps": 0},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 1, "hvps": 3},
            {"kind": "opt_iter", "coordinate": "fixed", "outer_iteration": 3,
             "iteration": 2, "hvps": 2, "evaluations": 3},
            {"kind": "re_fit_wave", "coordinate": "per-student",
             "outer_iteration": 3, "entities_fit": 10, "rows_useful": 500,
             "evals_sum": 40, "hvp_sum": 60, "hvp_wave": 90}]}
    assert bytes_needed("fe_hvp", ctx) == 5 * 2 * 36350 * 8
    assert bytes_needed("fe_pass", ctx) == 8 * 2 * 36350 * 8
    assert bytes_needed("fe_hot", ctx) == 8 * 2 * 20000 * 8
    assert bytes_needed("fe_cold", ctx) == 8 * 2 * 16350 * 8
    assert bytes_needed("tron_cg", ctx) == (5 * 2 * 36350 * 8
                                            + 60 * 50 * 4 * 2 * 4)
    assert bytes_needed("other", ctx) is None
    assert sweep_flops(ctx) == (4 * 8 * 36350 + 2 * 36350
                                + 4 * 100 * 50 * 4 + 2 * 1000 * 4)
    for r in ctx["ledger_rows"][1:4]:  # a program that counts no products
        del r["hvps"]
    assert bytes_needed("fe_hvp", ctx) is None and sweep_flops(ctx) is None
    assert bytes_needed("fe_pass", ctx) is None


selfchecks = (check_generator, check_work)
