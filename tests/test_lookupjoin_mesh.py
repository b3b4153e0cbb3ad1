"""BASELINE config 5 at upstream's shapes, small, on 4 of the 8 simulated
devices: ``orders.Join(custIndex, "cust_id")`` with orders(cust_id,
prod_id, qty, ts) streamed onto a mesh and people(id, name, surname)
indexed by a shuffled unique ``id`` — the query of the benchmark's
``lookupjoin-mesh4`` cell (``benchmark/queries/lookupjoin.py``).

The result is held to three oracles: a plain numpy reference kept here
(``row_of[cust]`` gathers over the generator's own arrays, no engine
code), the host executor, and the one-device run (bitwise).  The sizes
that select the streamed tier, the sample-sort and the all_to_all probe
are lowered in the test only.

The same three oracles hold the skewed stream of the benchmark's
``lookupjoin-mesh4-zipf`` cell (``ZipfCorpus``: Zipf(1.1) ``cust_id``
over a permuted rank -> customer map), where the probe's hot-key tier
answers the heavy customers and the exchange's capacity shrinks.
"""

import numpy as np
import pytest

import jax

from csvplus_tpu import FromFile, Take
from csvplus_tpu.obs.recompile import RecompileWatch
from csvplus_tpu.serve.plancache import PlanCache
from csvplus_tpu.utils.observe import telemetry

pytest.importorskip("csvplus_tpu.native.scanner")

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 of the simulated CPU devices"
)

N_PEOPLE, N_ORDERS, N_STOCK, SHARDS = 40_000, 100_000, 1_000, 4
FIRST = ("Amelia", "Olivia", "Emily", "Ava", "Isla", "Oliver", "Jack", "Harry", "Jacob", "Charlie")
LAST = ("Smith", "Jones", "Taylor", "Williams", "Brown", "Davies", "Evans", "Wilson", "Thomas",
        "Roberts", "Johnson", "Lewis")
COLUMNS = ("cust_id", "prod_id", "qty", "ts", "id", "name", "surname")


class Corpus:
    """Upstream's orders and people from a seed: numpy arrays and the
    two CSV files written from them."""

    def __init__(self, seed: int, root):
        rng = np.random.default_rng(seed)
        self.people_id = rng.permutation(N_PEOPLE)  # unique and shuffled: the index build sorts
        self.cust = self.draw_cust(rng)
        self.prod = rng.integers(0, N_STOCK, N_ORDERS)
        self.qty = rng.integers(1, 101, N_ORDERS)
        secs = rng.integers(0, 366 * 86400, N_ORDERS).astype("timedelta64[s]")
        stamps = np.datetime_as_string(np.datetime64("2016-01-01T00:00:00") + secs, unit="s")
        self.ts = np.char.add(stamps, "+01:00")  # 25 bytes, as the README prints it
        self.people = str(root / f"people{seed}.csv")
        self.orders = str(root / f"orders{seed}.csv")
        rows = np.arange(N_PEOPLE)
        self.name = np.array(FIRST)[rows % len(FIRST)]
        self.surname = np.array(LAST)[(rows // len(FIRST)) % len(LAST)]
        with open(self.people, "w") as f:
            f.write("id,name,surname\n")
            f.writelines(
                f"c{i},{n},{s}\n" for i, n, s in zip(self.people_id, self.name, self.surname)
            )
        with open(self.orders, "w") as f:
            f.write("cust_id,prod_id,qty,ts\n")
            f.writelines(
                f"c{c},p{p},{q},{t}\n" for c, p, q, t in zip(self.cust, self.prod, self.qty, self.ts)
            )

    def draw_cust(self, rng) -> np.ndarray:
        """Uniform over the people, every one occurring."""
        cust = rng.integers(0, N_PEOPLE, N_ORDERS)
        cust[rng.choice(N_ORDERS, N_PEOPLE, replace=False)] = np.arange(N_PEOPLE)
        return cust

    def want(self) -> dict:
        """The reference: each order beside the person its cust_id names;
        an order of a customer who is not among the people is dropped."""
        row_of = np.empty(N_PEOPLE, dtype=np.int64)
        row_of[self.people_id] = np.arange(N_PEOPLE)
        keep = self.cust < N_PEOPLE
        person = row_of[self.cust[keep]]
        cust = np.char.add("c", self.cust[keep].astype(str))
        return {
            "cust_id": cust, "prod_id": np.char.add("p", self.prod[keep].astype(str)),
            "qty": self.qty[keep].astype(str), "ts": self.ts[keep],
            "id": cust, "name": self.name[person], "surname": self.surname[person],
        }


LAYOUT = 39  # the skeleton's seed: which rows hold which rank, whatever the corpus's seed


class ZipfCorpus(Corpus):
    """``cust_id`` Zipf(*s*) over ranks 1..N_PEOPLE, rank -> customer by a
    seeded permutation (``benchmark/gen/orders_zipf.py``'s draw, kept
    plain here): a rank's row count is its expected count (the rounded
    running sum, no draw), a skeleton from the fixed ``LAYOUT`` says
    which rows hold which rank, and the corpus's seed which customer has
    which rank.  *heavy* moves that share of the orders onto rank 1;
    *absent* gives rank 1 to a customer who is not among the people."""

    def __init__(self, seed: int, root, s: float = 1.1, heavy: float = 0.0, absent: bool = False):
        self.s, self.heavy, self.absent = s, int(heavy * N_ORDERS), absent
        super().__init__(seed, root)

    def draw_cust(self, rng) -> np.ndarray:
        cum = np.cumsum(np.arange(1, N_PEOPLE + 1, dtype=np.float64) ** -self.s)
        edges = np.rint(cum * ((N_ORDERS - self.heavy) / cum[-1])).astype(np.int64)
        self.rank_rows = np.diff(edges, prepend=0)
        self.rank_rows[0] += self.heavy
        rank_of_row = np.repeat(np.arange(N_PEOPLE), self.rank_rows)
        np.random.default_rng(LAYOUT).shuffle(rank_of_row)
        self.customer_of_rank = rng.permutation(N_PEOPLE)
        if self.absent:
            self.customer_of_rank[0] = N_PEOPLE + 7
        return self.customer_of_rank[rank_of_row]


def lookup_join(corpus: Corpus, shards):
    """(result table, stage records of the first execution, index-build
    stages, kernels lowered by a second execution)."""
    with telemetry.collect() as records:
        orders = FromFile(corpus.orders).OnDevice(shards=shards)
        people = FromFile(corpus.people).OnDevice(shards=shards)
        cust_idx = people.UniqueIndexOn("id").sync()
        build = [r.stage for r in records]
    plan = orders.Join(cust_idx, "cust_id").plan
    cache = PlanCache()
    with telemetry.collect() as records:
        first = cache.execute(plan).sync()
        first_stages = list(records)
    with RecompileWatch() as watch:
        second = cache.execute(plan).sync()
    assert cache.stats()["lowered"] == 1
    assert column_strings(second) == column_strings(first)
    return orders.plan.table, first, first_stages, build, watch


def column_strings(table) -> dict:
    rows = table.to_rows()
    return {name: [r[name] for r in rows] for name in COLUMNS}


@pytest.fixture(autouse=True)
def small_thresholds(monkeypatch):
    import csvplus_tpu.ops.join as J
    import csvplus_tpu.ops.sort as S

    monkeypatch.setenv("CSVPLUS_STREAM_MIN_BYTES", "1")
    monkeypatch.setenv("CSVPLUS_STREAM_CHUNK_BYTES", str(512 * 1024))
    # ts crosses into device-lane dictionaries mid-stream, as its millions
    # of different values do at the benchmark's size
    monkeypatch.setenv("CSVPLUS_DICT_DEVICE_MIN_DISTINCT", "20000")
    monkeypatch.setattr(J.DeviceIndex, "PARTITION_MIN_KEYS", 1000)
    monkeypatch.setattr(S, "DSORT_MIN_ROWS", 1000)


def stage_extras(stages, name) -> list:
    return [r.extra for r in stages if r.stage == name]


def rows_per_owner(corpus: Corpus) -> np.ndarray:
    """How many orders each of the SHARDS owners is sent, by numpy: an
    id's packed key is the rank of ``c<id>`` among the people's ids in
    string order, an owner holds one equal run of those ranks, and an
    order of a customer who is not among the people goes nowhere."""
    order = np.argsort(np.arange(N_PEOPLE).astype(str))
    rank = np.empty(N_PEOPLE, dtype=np.int64)
    rank[order] = np.arange(N_PEOPLE)
    present = corpus.cust[corpus.cust < N_PEOPLE]
    return np.bincount(rank[present] // (N_PEOPLE // SHARDS), minlength=SHARDS)


def holds_the_count(exchange: dict, corpus: Corpus) -> None:
    """``pair_max`` is the fullest (source, owner) pair: at least the
    fullest owner's rows over the sources (some source holds the mean),
    at most all of them; a capacity that holds it read no flag."""
    fullest = int(rows_per_owner(corpus).max())
    assert -(-fullest // SHARDS) <= exchange["pair_max"] <= fullest
    if exchange["pair_max"] <= exchange["capacity"]:
        assert exchange["host_sync_elements"] in (0, 1)  # the broadcast count at most


def equals_three_oracles(corpus: Corpus, result) -> None:
    """*result* (the mesh run) against (a) the numpy reference, (b) the
    host executor and (c) the one-device run: the same values everywhere,
    and bitwise the same lanes and dictionaries wherever the two keep a
    column alike (the one-device index build demotes the typed id lane,
    the mesh sort does not)."""
    got = column_strings(result)
    for name, want in corpus.want().items():
        assert got[name] == want.tolist(), name
    host = Take(FromFile(corpus.orders)).Join(
        Take(FromFile(corpus.people)).UniqueIndexOn("id"), "cust_id"
    ).ToRows()
    assert got == {name: [r[name] for r in host] for name in COLUMNS}
    _, single, single_stages, single_build, _ = lookup_join(corpus, None)
    assert "dsort" not in single_build
    assert not stage_extras(single_stages, "join:all_to_all")
    assert result.nrows == single.nrows == len(host)
    assert got == column_strings(single)
    bitwise = []
    for name in COLUMNS:
        a, b = result.columns[name], single.columns[name]
        if type(a) is not type(b):
            continue
        np.testing.assert_array_equal(np.asarray(a.storage), np.asarray(b.storage), err_msg=name)
        if getattr(a, "kind", "str") != "int":
            np.testing.assert_array_equal(np.asarray(a.dictionary), np.asarray(b.dictionary))
        bitwise.append(name)
    assert set(bitwise) >= {"cust_id", "prod_id", "qty", "ts", "name", "surname"}


@pytest.mark.parametrize("seed", [11, 2_200_000_027, 4_100_000_123])
def test_lookup_join_on_the_mesh_equals_numpy_host_and_one_device(seed, tmp_path):
    corpus = Corpus(seed, tmp_path)
    fact, result, stages, build, watch = lookup_join(corpus, SHARDS)

    # the deployment's layout: stream pre-sharded, result left on the mesh
    assert getattr(fact, "_pre_sharded", False)
    for table in (fact, result):
        for col in table.columns.values():
            assert len(col.storage.sharding.device_set) == SHARDS
    # the mesh sort built the index and the exchange answered the probe
    assert "dsort" in build
    exchange = [r for r in stages if r.stage == "join:all_to_all"]
    assert len(exchange) == 1
    extra = exchange[0].extra
    assert extra["retries"] == 0 and extra["attempts"] == 1
    # the capacity is the count's: the pow2 bucket of the fullest pair, near the mean
    # pair of a uniform stream, half of what twice the mean gave; no flag is read
    from csvplus_tpu.parallel.pjoin import _default_capacity, _pow2

    holds_the_count(extra, corpus)
    assert extra["capacity_from"] == "count" and extra["host_sync_elements"] == 0
    assert extra["capacity"] == _pow2(extra["pair_max"]) == _default_capacity(N_ORDERS, SHARDS) // 2
    assert extra["slot_fill"] == pytest.approx(N_ORDERS / (SHARDS**2 * extra["capacity"]))
    assert extra["bytes_exchanged"] == 3 * 4 * SHARDS**2 * extra["capacity"]
    assert [r for r in stages if r.stage == "join:skew-detect"][0].extra["hot_keys"] == 0
    # people's ids are the codes of their own dictionary, so each shard owns one
    # contiguous run of keys: the owner answers by position, with no search
    (partition,) = [r.extra for r in stages if r.stage == "join:partition"]
    assert partition["positional"] is True and partition["span_max"] == N_PEOPLE // SHARDS
    assert extra["owner_tier"] == "positional" and extra["search_rounds"] == 0
    watch.assert_zero()  # the second execution lowered nothing

    equals_three_oracles(corpus, result)
    ts = result.columns["ts"]
    assert ts._lane_state is not None and len(ts.dictionary) == len(set(corpus.ts.tolist()))


@pytest.mark.parametrize("kind", ["dense", "sorted"])
def test_translation_tables_are_placed_on_the_probe_mesh_once(kind, tmp_path, monkeypatch):
    """The typed ``cust_id`` lane's translation into the index's id
    dictionary runs in every execution: its tables sit replicated on the
    stream's own four devices, committed, and are the same arrays call
    after call — left uncommitted on the default device, jit copies them
    whole onto every shard each time (two 20M-entry tables at the
    benchmark's size)."""
    from csvplus_tpu.columnar.typed import IntColumn

    if kind == "sorted":  # 40,000 ids over 40,000 slots are dense by every rule: refuse the table
        monkeypatch.setattr(IntColumn, "_dense_admitted", staticmethod(lambda size, lo, hi: False))
    corpus = Corpus(7, tmp_path)
    build_id = FromFile(corpus.people).OnDevice().plan.table.columns["id"]
    probes = {
        shards: FromFile(corpus.orders).OnDevice(shards=shards).plan.table.columns["cust_id"]
        for shards in (SHARDS, None)
    }
    state = probes[SHARDS].translation_state_to(build_id)
    assert state[0] == kind
    tables = [a for a in state if isinstance(a, jax.Array)]
    assert len(tables) == (1 if kind == "dense" else 2)
    on = probes[SHARDS].values.sharding.device_set
    assert len(on) == SHARDS
    for a in tables:
        assert a.committed and a.sharding.device_set == on and a.sharding.is_fully_replicated
    again = probes[SHARDS].translation_state_to(build_id)
    assert all(a is b for a, b in zip(state, again))
    # one device: the tables stay where the host parse put them
    single = probes[None].translation_state_to(build_id)
    assert all(len(a.sharding.device_set) == 1 for a in single if isinstance(a, jax.Array))
    np.testing.assert_array_equal(
        np.asarray(probes[SHARDS].renumbered_to_col(build_id)),
        np.asarray(probes[None].renumbered_to_col(build_id)),
    )
    assert (np.asarray(probes[None].renumbered_to_col(build_id)) >= 0).all()


ZIPF_CASES = {
    # name: (corpus arguments, hot keys at least, whose capacity settles: the count holds the
    # hot rows too, so the sketch's tail capacity is the smaller wherever a key is hot)
    "zipf-1.1-seed-11": (dict(seed=11), 1, "sketch"),
    "zipf-1.1-seed-2200000027": (dict(seed=2_200_000_027), 1, "sketch"),
    "zipf-1.1-seed-4100000123": (dict(seed=4_100_000_123), 1, "sketch"),
    "one-key-over-60-percent": (dict(seed=5, heavy=0.62), 1, "sketch"),  # an eighth of the count's
    "hot-key-absent-from-the-build-side": (dict(seed=6, absent=True), 0, "count"),
}


@pytest.mark.parametrize("case", sorted(ZIPF_CASES))
def test_skewed_lookup_join_on_the_mesh_equals_numpy_host_and_one_device(case, tmp_path):
    from csvplus_tpu.parallel.pjoin import _pow2, _skew_capacity

    kwargs, hot_at_least, capacity_from = ZIPF_CASES[case]
    corpus = ZipfCorpus(root=tmp_path, **kwargs)
    fact, result, stages, build, watch = lookup_join(corpus, SHARDS)

    assert getattr(fact, "_pre_sharded", False) and "dsort" in build
    for col in result.columns.values():
        assert len(col.storage.sharding.device_set) == SHARDS
    (detect,) = stage_extras(stages, "join:skew-detect")
    (exchange,) = stage_extras(stages, "join:all_to_all")
    assert detect["hot_keys"] >= hot_at_least
    assert detect["host_sync_elements"] >= detect["sample"] > 0
    # the hot keys are the heaviest customers that exist on the build side, and the
    # broadcast tier answered exactly their orders: the exchange carried the rest
    present = corpus.customer_of_rank < N_PEOPLE
    hot_rows = int(corpus.rank_rows[present][: detect["hot_keys"]].sum())
    if detect["hot_keys"]:
        (broadcast,) = stage_extras(stages, "join:broadcast")
        (skew,) = stage_extras(stages, "join:skew")
        assert broadcast["n_hot"] >= detect["hot_keys"]
        assert skew["hot_keys"] == detect["hot_keys"]
        assert skew["rows_broadcast"] == hot_rows
        assert skew["rows_repartitioned"] == N_ORDERS - hot_rows
        assert skew["capacity"] == exchange["capacity"]
        # the broadcast count, and with it the overflow flag unless the count guarantees the capacity
        guaranteed = exchange["pair_max"] <= exchange["capacity"]
        assert exchange["host_sync_elements"] == (1 if guaranteed else 2) * exchange["attempts"]
    else:
        assert not stage_extras(stages, "join:broadcast") and not stage_extras(stages, "join:skew")
    # the first attempt's capacity held: the counted bucket, or the sketch's where it is smaller
    holds_the_count(exchange, corpus)
    counted = _pow2(exchange["pair_max"])
    sketch = _skew_capacity(N_ORDERS, SHARDS, detect["hot_share"]) if detect["hot_keys"] else counted
    assert exchange["retries"] == 0 and exchange["attempts"] == 1
    assert exchange["capacity"] == min(counted, sketch)
    assert exchange["capacity_from"] == capacity_from == ("sketch" if sketch < counted else "count")
    if case == "one-key-over-60-percent":  # one source sends the key's owner 62% of its rows
        assert exchange["capacity"] == counted // 8
    assert exchange["slot_fill"] == pytest.approx(N_ORDERS / (SHARDS**2 * exchange["capacity"]))
    assert exchange["owner_tier"] == "positional"
    watch.assert_zero()  # the second execution lowered nothing, the hot answers' program included
    assert result.nrows == int(corpus.rank_rows[present].sum())
    equals_three_oracles(corpus, result)  # whatever the retries, the result is exact


def test_a_second_seed_of_one_skewed_layout_lowers_nothing(tmp_path):
    """Which customer is hot is the seed's; how many are, the exchange's
    capacity and so every program of the join are the layout's: a second
    seed's executions, the first included, find every kernel lowered."""
    first = ZipfCorpus(11, tmp_path)
    _, _, stages, _, _ = lookup_join(first, SHARDS)
    second = ZipfCorpus(4_100_000_123, tmp_path)
    assert first.customer_of_rank[0] != second.customer_of_rank[0]
    assert (first.rank_rows == second.rank_rows).all()
    orders = FromFile(second.orders).OnDevice(shards=SHARDS)
    cust_idx = FromFile(second.people).OnDevice(shards=SHARDS).UniqueIndexOn("id").sync()
    plan = orders.Join(cust_idx, "cust_id").plan
    cache = PlanCache()
    with RecompileWatch() as watch, telemetry.collect() as records:
        result = cache.execute(plan).sync()
        again = list(records)
        cache.execute(plan).sync()
    watch.assert_zero("a second seed of one layout")
    for name in ("join:skew-detect", "join:broadcast", "join:skew"):
        (a,), (b,) = stage_extras(stages, name), stage_extras(again, name)
        assert {k: v for k, v in a.items() if k != "wait_s"} == {k: v for k, v in b.items() if k != "wait_s"}, name
    assert stage_extras(stages, "join:all_to_all")[0]["capacity"] == stage_extras(again, "join:all_to_all")[0]["capacity"]
    got = column_strings(result)
    for name, want in second.want().items():
        assert got[name] == want.tolist(), name
