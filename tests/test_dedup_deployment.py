"""BASELINE config 4 at small sizes: ``IndexOn(key)`` then
``ResolveDuplicates(policy)`` over a people file with repeated keys
(names go by the row's number, so the copies of one key differ in
payload and a wrong pick or an unstable sort shows).

The device path is held to two references that share nothing with it: a
plain one computed from the generated rows alone (stable-sort the rows
by the key's strings, keep the first / last of each run) and the host
executor (upstream's algorithm row by row, through the callback form).
The stages the build and the resolution record, the elements they read
to the host and the second execution's zero lowerings are pinned too.
The full-size deployment is ``benchmark/configs/people-dedup-50m.json``.
"""

import numpy as np
import pytest

from csvplus_tpu import Take, from_file, load_index
from csvplus_tpu.columnar.table import DeviceTable
from csvplus_tpu.obs.recompile import RecompileWatch
from csvplus_tpu.ops import sort as sort_ops
from csvplus_tpu.utils.observe import telemetry

from conftest import PEOPLE_NAMES, PEOPLE_SURNAMES

DEPTS = ("dev", "hr", "ops")


def _sizes(kind: str, ids: int) -> np.ndarray:
    """How often each of *ids* keys occurs."""
    if kind == "none":  # duplicate share 0
        return np.ones(ids, dtype=np.int64)
    if kind == "tenth":  # the configuration's: 1 key in 9 twice, 10% of the rows repeats
        return np.where(np.arange(ids) % 9 == 0, 2, 1)
    if kind == "all":  # duplicate share 100%: no key occurs once
        return np.full(ids, 2, dtype=np.int64)
    assert kind == "mixed"  # 1 to 5 copies
    return 1 + np.arange(ids) % 5


def _people(
    tmp_path, key="c%d", sizes="tenth", placement="scattered", ids=450, two_column=False, seed=20160914,
    payload=None, row_values=None,
):
    """(path, rows as dicts in file order, key columns): a seeded people
    file.  *placement*: ``scattered`` shuffles every row, ``adjacent``
    writes a key's copies one after another, ``far`` writes every key
    once and then the copies, so a pair lies about half a file apart.
    *row_values* gives the rows' key values outright; *payload* replaces
    (name, surname) by that many columns ``p0``…, numbers and names in
    turn, all by the row's number."""
    rng = np.random.default_rng(seed)
    count = _sizes(sizes, ids)
    values = rng.permutation(ids)  # which keys repeat
    if row_values is not None:
        row_values = np.asarray(row_values)
    elif placement == "far":
        row_values = np.concatenate(
            [rng.permutation(values)] + [rng.permutation(values[count > k]) for k in range(1, int(count.max()))]
        )
    else:
        row_values = np.repeat(values, count)
        if placement == "scattered":
            rng.shuffle(row_values)
    rows = []
    for r, v in enumerate(row_values.tolist()):
        row = {"dept": DEPTS[v % 3]} if two_column else {}
        row["id"] = key % v
        if payload is None:
            row.update(name=PEOPLE_NAMES[r % 10], surname=PEOPLE_SURNAMES[(r // 10) % 12])
        else:
            row.update((f"p{j}", PEOPLE_NAMES[(r + j) % 10] if j % 2 else str(r * (j + 3))) for j in range(payload))
        rows.append(row)
    path = tmp_path / "people.csv"
    with open(path, "w") as f:
        f.write(",".join(rows[0]) + "\n")
        f.writelines(",".join(row.values()) + "\n" for row in rows)
    return str(path), rows, (["dept", "id"] if two_column else ["id"])


def _reference(rows, key, policy):
    """The plain reference: the rows stable-sorted by the key's strings
    (ASCII here, so Python's str order is upstream's byte order), of
    each equal-key run the first (last) row."""
    out = []
    for row in sorted(rows, key=lambda r: [r[c] for c in key]):  # sorted() is stable
        if out and all(out[-1][c] == row[c] for c in key):
            if policy == "last":
                out[-1] = row
        else:
            out.append(row)
    return out


def _dedup(src, key, resolve):
    index = src.index_on(*key)
    index.resolve_duplicates(resolve)
    return index


def _rows(index):
    return [dict(r) for r in Take(index).to_rows()]


# key: "c%d" is a typed int lane whose index build demotes it; "k%x" is
# hex, not all cells decimal, so a dictionary column from the start
CASES = {
    "typed-id-tenth": dict(),
    "typed-id-no-duplicates": dict(sizes="none"),
    "typed-id-all-duplicated": dict(sizes="all"),
    "typed-id-mixed-1-to-5": dict(sizes="mixed"),
    "string-key-tenth": dict(key="k%x"),
    "string-key-mixed-1-to-5": dict(key="k%x", sizes="mixed"),
    "copies-adjacent": dict(sizes="all", placement="adjacent"),
    "copies-adjacent-mixed": dict(key="k%x", sizes="mixed", placement="adjacent"),
    "copies-far-apart": dict(sizes="tenth", placement="far"),
    "copies-far-apart-mixed": dict(sizes="mixed", placement="far"),
    "two-columns-string-typed": dict(two_column=True, sizes="mixed"),
    "two-columns-string-string": dict(two_column=True, key="k%x"),
}


@pytest.mark.parametrize("policy", ["first", "last"])
@pytest.mark.parametrize("case", list(CASES))
def test_device_dedup_equals_the_plain_reference_and_the_host_executor(tmp_path, case, policy):
    path, rows, key = _people(tmp_path, **CASES[case])
    people = from_file(path).on_device("cpu")
    kinds = {c: people.plan.table.columns[c].kind for c in key}
    assert kinds["id"] == ("str" if "k%x" in CASES[case].values() else "int"), kinds
    dev = _dedup(people, key, policy)
    assert dev._impl.is_lazy and dev._impl.dev is not None  # stayed on the device
    want = _reference(rows, key, policy)
    assert len(dev) == len(want)
    assert _rows(dev) == want
    pick = (lambda g: g[0]) if policy == "first" else (lambda g: g[-1])
    assert _rows(_dedup(Take(from_file(path)), key, pick)) == want
    if CASES[case].get("sizes") != "none":
        # the copies of a repeated key really differ, so a wrong pick would show
        assert _reference(rows, key, "last" if policy == "first" else "first") != want


@pytest.mark.parametrize("use", ["find", "join", "unique-check", "persist"])
def test_the_deduplicated_index_serves(tmp_path, use):
    """What upstream does with a resolved index: Find, Join against it,
    the duplicate check of a unique index, WriteTo / load."""
    path, rows, key = _people(tmp_path, sizes="mixed")
    want = _reference(rows, key, "first")
    people = from_file(path).on_device("cpu")
    dev = _dedup(people, key, "first")
    host = _dedup(Take(from_file(path)), key, "first")
    if use == "find":
        for probe in ("c0", "c7", "c449", "c450"):  # c450: no such id
            got = [dict(r) for r in dev.find(probe).to_rows()]
            assert got == [r for r in want if r["id"] == probe] == [dict(r) for r in host.find(probe).to_rows()]
    elif use == "join":
        orders = tmp_path / "orders.csv"
        orders.write_text("cust_id,qty\n" + "".join(f"c{v},{v % 7}\n" for v in (3, 449, 3, 999, 0, 120)))
        joined = from_file(str(orders)).on_device("cpu").join(dev, "cust_id").to_rows()
        assert joined == Take(from_file(str(orders))).join(host, "cust_id").to_rows()
        assert len(joined) == 5  # one row per order whose id exists: no copy joins twice
    elif use == "unique-check":
        before = people.index_on(*key)._impl.dev.table
        assert sort_ops.find_adjacent_duplicate(before, key) is not None
        assert sort_ops.find_adjacent_duplicate(dev._impl.dev.table, key) is None
    else:
        saved = str(tmp_path / "people.idx")
        dev.write_to(saved)
        loaded = load_index(saved, device="cpu")
        assert loaded._impl.is_lazy and len(loaded) == len(want) and _rows(loaded) == want


def test_named_policy_stays_on_the_device_and_says_so(tmp_path, monkeypatch):
    """Stages, extras and host reads of one execution: the run mask is
    never read to the host (``run_starts`` is the callback's), the one
    host read is the kept rows' count, and it is counted."""
    path, rows, key = _people(tmp_path)
    people = from_file(path).on_device("cpu")
    monkeypatch.setattr(sort_ops, "run_starts", lambda *a: pytest.fail("run_starts: the mask crossed the host"))
    with telemetry.collect() as records:
        _dedup(people, key, "first").sync()
        stages = [r.stage for r in records]
        by = {r.stage: r for r in records}  # the last record of each name
        synced = telemetry.host_sync_elements
    ours = [r for r in records if r.stage.startswith(("index:", "dedup:"))]
    assert [r.stage for r in ours] == [
        "index:view", "index:sort", "index:permute", "index:pack",
        "dedup:runs", "dedup:compact", "index:pack",
    ]
    assert "typed:demote" in stages  # the first execution only (below)
    n, kept = len(rows), len(_reference(rows, key, "first"))
    assert all(r.extra["rows"] == (kept if r is ours[-1] else n) for r in ours)
    sort = by["index:sort"].extra
    assert (sort["keys"], sort["tier"]) == (1, "lax")
    assert by["index:permute"].extra["row_gathers"] == 2  # name, surname; the key comes sorted
    compact = by["dedup:compact"]
    assert compact.extra["tier"] == "device" and compact.extra["policy"] == "first"
    assert compact.extra["kept"] == compact.rows_out == by["dedup:runs"].rows_out == kept
    # the survivors of all three lanes ride one sort on (drop flag, row number): nothing is gathered but the permutation
    assert (compact.extra["row_gathers"], compact.extra["form"], compact.extra["lanes"]) == (0, "sort", 3)
    assert sum(r.extra["row_gathers"] for r in ours) == 2
    # the resolution reads one scalar to the host, the count; it is counted
    assert sum(r.extra.get("host_sync_elements", 0) for r in ours) == synced == 1
    for r in ours[1:]:
        assert r.extra["synced"] is True and "wait_s" in r.extra


def test_callback_path_groups_on_the_host_and_decodes_only_duplicate_groups(tmp_path, monkeypatch):
    path, rows, key = _people(tmp_path)
    masks, decoded = [], []
    real_starts, real_to_rows = sort_ops.run_starts, DeviceTable.to_rows

    def spy_starts(table, cols):
        masks.append(real_starts(table, cols))
        return masks[-1]

    def spy_to_rows(self, sel=None):
        decoded.append(self.nrows if sel is None else len(sel))
        return real_to_rows(self, sel)

    monkeypatch.setattr(sort_ops, "run_starts", spy_starts)
    monkeypatch.setattr(DeviceTable, "to_rows", spy_to_rows)
    dev = from_file(path).on_device("cpu").index_on(*key)
    seen = []
    with telemetry.collect() as records:
        dev.resolve_duplicates(lambda g: seen.append(len(g)) or g[0])
        compact = [r.extra for r in records if r.stage == "dedup:compact"]
        synced = telemetry.host_sync_elements
    monkeypatch.undo()
    assert len(masks) == 1 and masks[0].dtype == bool and masks[0].shape == (len(rows),)
    assert seen == [2] * 50 and decoded == [100]  # the 50 doubled ids' rows, nothing else
    assert [c["tier"] for c in compact] == ["host"] and compact[0]["policy"] == "callback"
    assert synced >= len(rows)  # the mask is counted, one bool a row
    assert dev._impl.is_lazy and _rows(dev) == _reference(rows, key, "first")


def test_second_execution_lowers_nothing_and_does_not_demote_again(tmp_path):
    path, rows, key = _people(tmp_path)
    people = from_file(path).on_device("cpu")
    first = _dedup(people, key, "first").sync()
    with RecompileWatch() as w, telemetry.collect() as records:
        second = _dedup(people, key, "first").sync()
        stages = [r.stage for r in records]
    w.assert_zero("second IndexOn + ResolveDuplicates on the same table")
    assert "typed:demote" not in stages and "index:sort" in stages
    assert _rows(second) == _rows(first)


def _shape_values(shape, key, two_column):
    """Key values by row for an edge shape of the compaction."""
    if shape == "mixed":  # 1 to 5 copies of 40 keys, shuffled
        return None
    if shape == "no-duplicate":
        return [5, 3, 9, 1, 7, 11, 0]
    if shape == "one-key":  # every row one key
        return [7] * 6
    if shape == "n2":
        return [5, 5]
    if shape == "n1":  # the early return: a single row has no run to resolve
        return [5]
    assert shape == "ends"  # only the least and the greatest key of the index order repeat
    order = sorted(range(12), key=lambda v: ((DEPTS[v % 3],) if two_column else ()) + (key % v,))
    return [4, order[-1], 8, order[0], 2, order[0], 6, 10, order[-1], 0, 1, 3, 5, 7, 9, 11]


KEYS = {"typed": dict(key="c%d"), "string": dict(key="k%x"), "two-column": dict(key="k%x", two_column=True)}


@pytest.mark.parametrize("policy", ["first", "last"])
@pytest.mark.parametrize("payload", [1, 3, 9])
@pytest.mark.parametrize("key_kind", list(KEYS))
@pytest.mark.parametrize("shape", ["mixed", "no-duplicate", "one-key", "ends", "n2", "n1"])
def test_sort_compaction_equals_the_gather_it_replaced_and_the_host_executor(
    tmp_path, shape, key_kind, payload, policy
):
    """``compact_runs`` (every lane rides one sort on (drop flag, row
    number)) bit for bit against the form it replaced, kept here as the
    reference — ``table.gather`` by ``np.flatnonzero`` of the host's run
    mask — and, through ``resolve_duplicates``, against the host
    executor."""
    values = _shape_values(shape, KEYS[key_kind]["key"], KEYS[key_kind].get("two_column", False))
    path, rows, key = _people(
        tmp_path, sizes="mixed", ids=40, payload=payload, row_values=values, **KEYS[key_kind]
    )
    people = from_file(path).on_device("cpu")
    table = people.index_on(*key)._impl.dev.table  # sorted by the key
    assert table.columns["p0"].kind == "int" and (payload == 1 or table.columns["p1"].kind == "str")
    keep = sort_ops.run_starts(table, key)
    if policy == "last":
        keep = np.append(keep[1:], True)
    with telemetry.collect() as records:
        got = sort_ops.compact_runs(table, key, policy)
        stages = {r.stage: r.extra for r in records}
    if shape == "n1":
        assert got is None and not stages  # nothing dispatched
    elif shape == "no-duplicate":
        assert got is None and list(stages) == ["dedup:runs"]  # nothing sorted
    else:
        want = table.gather(np.flatnonzero(keep))
        assert got.nrows == want.nrows == int(keep.sum()) < table.nrows
        assert list(got.columns) == list(want.columns)
        for name, col in got.columns.items():
            assert type(col) is type(want.columns[name])
            lane, ref = np.asarray(col.storage), np.asarray(want.columns[name].storage)
            assert lane.dtype == ref.dtype == np.int32 and np.array_equal(lane, ref), name
        assert got.to_rows() == want.to_rows()
        extra = stages["dedup:compact"]
        assert (extra["form"], extra["lanes"], extra["row_gathers"]) == ("sort", len(table.columns), 0)
    want_rows = _reference(rows, key, policy)
    pick = (lambda g: g[0]) if policy == "first" else (lambda g: g[-1])
    assert _rows(_dedup(people, key, policy)) == want_rows == _rows(_dedup(Take(from_file(path)), key, pick))


@pytest.mark.parametrize("policy", ["first", "last"])
def test_a_mesh_sharded_table_compacts_by_the_same_sort(tmp_path, policy):
    """One form: rows sharded over the 8-device mesh (121 rows, padded to
    128) ride the same sort as a single device's."""
    path, rows, key = _people(tmp_path, sizes="mixed", ids=41)
    people = from_file(path).on_device("cpu", shards=8)
    assert len(rows) == 121
    with telemetry.collect() as records:
        dev = _dedup(people, key, policy)
        forms = [(r.extra["form"], r.extra["lanes"]) for r in records if r.stage == "dedup:compact"]
    assert forms == [("sort", 3)] and _rows(dev) == _reference(rows, key, policy)


def test_the_programs_the_yardstick_reads_still_run(tmp_path):
    """What ``kernel.index_sort_*`` and ``kernel.dedup_gather_*`` time in
    ``dedup-resident`` (``PERF.md`` §3): an ``IndexOn`` of a non-unique
    key and a ``ResolveDuplicates("first")`` dispatch ``csvplus.index.sort``
    once, ``csvplus.table.gather_take`` once per column the sort does not
    return, and ``csvplus.dedup.compact`` once.  On a row count no other
    test uses each lowers exactly once more."""
    path, rows, key = _people(tmp_path, key="k%x", row_values=[(i * 7) % 1000 for i in range(1283)])  # 283 ids twice
    people = from_file(path).on_device("cpu")
    with RecompileWatch() as w, telemetry.collect() as records:
        index = _dedup(people, key, "first").sync()
        by = {}
        for r in records:
            by.setdefault(r.stage, []).append(r.extra)
    grew = w.delta()
    assert len(index) == 1000
    assert {k: grew.get(k) for k in ("index.sort", "table.gather_take", "dedup.compact", "dedup.head")} == {
        "index.sort": 1, "table.gather_take": 1, "dedup.compact": 1, "dedup.head": 1,
    }  # the two payload gathers share one lowering
    assert [e["tier"] for e in by["index:sort"]] == ["lax"]
    assert [e["row_gathers"] for e in by["index:permute"]] == [2]
    assert [(e["form"], e["lanes"], e["row_gathers"]) for e in by["dedup:compact"]] == [("sort", 3, 0)]


def test_without_duplicates_nothing_is_gathered(tmp_path):
    path, rows, key = _people(tmp_path, sizes="none", ids=40)
    index = from_file(path).on_device("cpu").index_on(*key)
    before = index.device_table
    with telemetry.collect() as records:
        index.resolve_duplicates("first")
        stages = [(r.stage, r.extra["row_gathers"]) for r in records]
        synced = telemetry.host_sync_elements
    assert stages == [("dedup:runs", 0)] and synced == 1 and index.device_table is before
    assert _rows(index) == _reference(rows, key, "first")
