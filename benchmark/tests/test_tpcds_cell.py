"""The TPC-DS Q3 consumer against its plain reference, tiny, on the CPU:
results only. The whole-cell case goes through ``run_cell`` with real
role processes, 4 MiB blocks and fact files of 32,768 rows, the
published schema and Q3 as written (full-size dimensions); its tiny
configuration is written into the test's own directory
(``tests/data/tiny`` is the accepted cells')."""

import argparse
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.harness.discover import BENCH_DIR, load_module

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny")
BLOCK = 4 << 20
ROWS_PER_FILE = 32_768
ROWS = 3 * ROWS_PER_FILE + 5_000  # 4 files, the last one short
SEED = 2**31 + 42


def _config_and_traffic() -> tuple:
    with open(os.path.join(BENCH_DIR, "configs", "tpcds-ss-sf100.json")) as f:
        config = json.load(f)
    config.update(block_bytes=BLOCK, rows=ROWS, rows_per_file=ROWS_PER_FILE,
                  writer_threads=2)
    with open(os.path.join(BENCH_DIR, "traffic", "q3-scan.json")) as f:
        traffic = json.load(f)
    traffic.update(files=4, warm_row_groups=2, ring_steps=64, write_runs=2,
                   cold_starts={"min": 3, "seconds": 0.1, "max": 3},
                   trace_seconds=0.5)
    return config, traffic


def _tiny(tmp_path) -> tuple:
    """``(spec, dir)`` of a one-cell benchmark."""
    base = tmp_path / "bench"
    for sub in ("configs", "traffic"):
        (base / sub).mkdir(parents=True)
    config, traffic = _config_and_traffic()
    (base / "configs" / "tiny-tpcds.json").write_text(json.dumps(config))
    (base / "traffic" / "q3.json").write_text(json.dumps(traffic))
    with open(os.path.join(TINY, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"] = [{"name": "tiny.q3", "config": "tiny-tpcds",
                          "traffic": "q3", "chips": 1, "why": "rehearsal"}]
    for m in spec["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.q3"]
    return spec, str(base)


def _run(tmp_path, spec, base):
    shm = tmp_path / "shm"
    shm.mkdir()
    args = argparse.Namespace(workload="tiny.q3", seed=SEED, seconds=1.0,
                              trace=0)
    result = run.run_cell(
        args, spec=spec, configs_dir=base, traffic_dir=base,
        peaks_path=os.path.join(TINY, "peaks.json"), platform="cpu",
        shm=str(shm))
    assert os.listdir(shm) == []  # roles stopped, nothing left
    return result


def test_cell_runs_tiny_and_is_correct(tmp_path, capsys):
    spec, base = _tiny(tmp_path)
    result = _run(tmp_path, spec, base)
    assert result["correct"] is True and result["failed"] == 0
    assert {"step_gbps", "first_batch_ms", "setup_s"} <= \
        set(result["metrics"])
    assert all(m["value"] > 0 for m in result["metrics"].values())
    lines = {line.split(" ", 2)[1]: json.loads(line.split(" ", 2)[2])
             for line in capsys.readouterr().out.splitlines()
             if line.startswith("[bench]")}
    notes = lines["check"]["notes"]
    assert notes["steps"] > 4  # the window made steps after the warm-up
    assert notes["files_checked"] == 4 and notes["answer_equal"] is True
    assert notes["totals_equal"] is True and notes["totals_off"] == 0
    assert notes["rows_in_groups"] > 0 and notes["answer_rows"] > 0
    assert notes["row_group_equal"] == [2, True]
    counters = lines["window"]["counters"]
    assert counters["Client.JaxTableRows"] > 0
    assert "Client.JaxTablePlanMisses" not in counters  # never moved
    share = counters["Client.JaxTableFetchedBytes"] \
        / counters["Client.JaxTableRowGroupBytes"]
    assert 0.05 < share < 0.25  # Q3's three columns of 23
    assert lines["ingest"]["files"] == 6  # 4 fact files + 2 dimensions


def test_every_cold_start_gives_back_what_the_process_carried(
        tmp_path, monkeypatch):
    """A cold start is a new job's process: before its clock starts the
    collector runs and Arrow's pool gives back its freed pages, once a
    start, so no start inherits the window's or the last start's."""
    import gc

    import pyarrow as pa

    events = []
    pool = pa.default_memory_pool()

    class Pool:
        def release_unused(self):
            events.append("release")
            pool.release_unused()

        def __getattr__(self, name):
            return getattr(pool, name)

    collect = gc.collect
    monkeypatch.setattr(pa, "default_memory_pool", lambda: Pool())
    monkeypatch.setattr(gc, "collect",
                        lambda *a: events.append("collect") or collect(*a))
    spec, base = _tiny(tmp_path)
    result = _run(tmp_path, spec, base)
    assert result["correct"] is True
    # three cold starts, each collected and then released
    assert events.count("release") == 3
    assert [e for e in events if e == "release" or e == "collect"][-6:] \
        == ["collect", "release"] * 3


def test_a_program_without_row_groups_exits_before_the_ingest(
        tmp_path, monkeypatch):
    from alluxio_tpu.client.jax_io import DeviceBlockLoader

    monkeypatch.delattr(DeviceBlockLoader, "row_groups")
    spec, base = _tiny(tmp_path)
    with pytest.raises(SystemExit, match="no row_groups"):
        _run(tmp_path, spec, base)


@pytest.fixture()
def consumer():
    """The consumer at tiny size, its device state built by ``open``
    from the generated dimensions, without a cluster."""
    import jax

    mod = load_module("consumers", "tpcds_q3")
    config, traffic = _config_and_traffic()
    c = mod.Consumer(config=config, traffic=traffic, seed=SEED,
                     devices=jax.devices()[:1], roles=None)
    c.new_loader = lambda fs: None
    look = c.reference_lookups()
    c._lookups = lambda fs: (look, jax.device_put(mod.device_lookups(look)))
    c.open(None)
    return mod, c


def _landed(mod, c, i: int):
    """Fact file ``i``'s row group as the loader must land it."""
    from alluxio_tpu.client.jax_io import RowGroup

    cols = c.dataset.fact_columns(i, mod.COLUMNS)
    n = c.dataset.file_rows(i)
    values, valid = {}, {}
    for name in mod.COLUMNS:
        vals, ok = cols[name]
        v = np.zeros(c.rows, np.int32)
        v[:n] = vals if ok is None else np.where(ok, vals, 0)
        values[name] = v
        if ok is not None:
            m = np.zeros(c.rows, bool)
            m[:n] = ok
            valid[name] = m
    return RowGroup(values, valid, np.int32(n))


def _in_a_group(mod, c, i: int) -> int:
    """A row of fact file ``i`` that lands in a Q3 group."""
    cols = c.dataset.fact_columns(i, mod.COLUMNS)
    look = c.look
    ys = mod._look(look.date, cols["ss_sold_date_sk"][0] - look.date_base)
    bs = mod._look(look.item, cols["ss_item_sk"][0] - look.item_base)
    hit = cols["ss_sold_date_sk"][1] & cols["ss_ext_sales_price"][1] \
        & (ys >= 0) & (bs >= 0)
    return int(np.flatnonzero(hit)[0])


@pytest.mark.parametrize("spoil", [None, "price", "null", "num_rows",
                                   "order", "kept"])
def test_check_holds_every_partial_to_the_reference(consumer, spoil):
    import jax

    mod, c = consumer
    steps = [s % c.dataset.n_fact for s in range(6)]  # 1.5 passes
    groups = [_landed(mod, c, i) for i in steps]
    r = _in_a_group(mod, c, steps[3])
    if spoil == "price":
        groups[3].values["ss_ext_sales_price"][r] += 1
    elif spoil == "null":
        groups[3].valid["ss_ext_sales_price"][r] = False
    elif spoil == "num_rows":
        # the last file's padding is no row: a live mask there moves
        # no sum (its value is 0) but a count
        last = c.dataset.n_fact - 1
        n = c.dataset.file_rows(last)
        groups[last].valid["ss_sold_date_sk"][n] = True
        groups[last].valid["ss_ext_sales_price"][n] = True
        groups[last].values["ss_sold_date_sk"][n] = \
            groups[last].values["ss_sold_date_sk"][r]
        groups[last].values["ss_item_sk"][n] = groups[3].values[
            "ss_item_sk"][r]
        groups[last] = groups[last]._replace(num_rows=np.int32(n + 1))
    elif spoil == "order":
        groups[1], groups[2] = groups[2], groups[1]
    for s, g in enumerate(groups):
        c.step(jax.device_put(g))
        if spoil == "kept" and s == c.warm_items:
            wrong = g.values["ss_item_sk"].copy()
            wrong[0] += 1
            c._kept = (s, jax.device_put(g._replace(
                values={**g.values, "ss_item_sk": wrong})))
    got = c.check()
    assert got["notes"]["steps"] == 6
    if spoil is None:
        assert got["failed"] == 0, got
        assert got["notes"]["row_group_equal"] == [2, True]
        assert got["notes"]["answer_equal"] is True
        assert got["notes"]["totals_equal"] is True
    else:
        assert got["failed"] >= 1, got
    if spoil in ("price", "null", "num_rows"):
        # a wrong row moves the pass's totals as well as its partial
        assert got["notes"]["totals_equal"] is False


def test_a_cold_start_adds_nothing_to_the_pass_totals(consumer):
    import jax

    mod, c = consumer
    for i in range(3):
        c.step(jax.device_put(_landed(mod, c, i)))
    c._run(jax.device_put(_landed(mod, c, 0)), c._luts, c.ring)
    got = c.check()
    assert got["failed"] == 0 and got["notes"]["totals_equal"] is True


@pytest.mark.parametrize("acc", ["int64", "float32", "int32"])
def test_the_pass_totals_are_exact_only_in_int64(consumer, acc):
    """Every row of a row group in one group at ~3 M cents: a step's
    group passes float32's exact integers (2**24) and int32 (2**31),
    as a group's total over a pass and the pass's total do at SF100;
    the totals the step keeps on the device equal NumPy's int64 sums
    only where it sums in int64."""
    import jax

    mod, c = consumer
    step = mod.make_step(c.rows, c.look, c.ring, acc=acc)
    with jax.enable_x64(True):
        state = mod.new_state(c.ring, c.look.n_groups, jax.devices()[0],
                              acc=acc)
    rg = _landed(mod, c, 0)
    r = _in_a_group(mod, c, 0)
    n = c.dataset.file_rows(0)
    for name in mod.COLUMNS:
        rg.values[name][:n] = rg.values[name][r]
    for mask in rg.valid.values():
        mask[:n] = True
    rg.values["ss_ext_sales_price"][:n] = 2_999_999 - 2 * (np.arange(n) % 97)
    look = c.look
    group = (mod._look(look.date, rg.values["ss_sold_date_sk"][r:r + 1]
                       - look.date_base)[0] * len(look.brands)
             + mod._look(look.item, rg.values["ss_item_sk"][r:r + 1]
                         - look.item_base)[0])
    steps = 3
    with jax.enable_x64(True):
        for s in range(steps):
            state, _count = step(jax.device_put(rg), *c._luts, state,
                                 np.int32(s))
        _ring, (sums, tally, whole) = jax.device_get(state)
    want = steps * int(rg.values["ss_ext_sales_price"][:n].astype(
        np.int64).sum())
    assert want > 2**31
    assert int(tally[group]) == steps * n and int(tally.sum()) == steps * n
    exact = (np.array_equal(np.flatnonzero(sums), [group])
             and int(sums[group]) == want and int(whole) == want)
    assert exact is (acc == "int64")


def test_the_step_has_one_shape_for_every_seed(consumer):
    """The groups are every year by every brand and the runs are padded,
    so one compiled program serves every seed."""
    mod, c = consumer
    config, traffic = _config_and_traffic()
    shapes = set()
    for seed in (SEED, 7, 2**33 + 5):
        other = mod.Consumer(config=config, traffic=traffic, seed=seed,
                             devices=[c.device], roles=None)
        look = other.reference_lookups()
        assert look.n_groups == 201 * mod.BRANDS
        shapes.add(tuple(x.shape for run in mod.device_lookups(look)
                         for x in run))
    assert shapes == {((mod.RUNS_PAD,),) * 6}


def test_the_reference_answer_is_q3_as_sql_reads_it(consumer):
    """Q3 over two files by the plain row-at-a-time definition: join,
    filter, group, order, limit, against ``answer`` of the partials."""
    mod, c = consumer
    ds = c.dataset
    date = ds.date_columns()
    item = ds.item_columns()
    year_of = dict(zip(date["d_date_sk"].tolist(), date["d_year"].tolist()))
    moy_of = dict(zip(date["d_date_sk"].tolist(), date["d_moy"].tolist()))
    sums: dict = {}
    for i in (0, 3):
        cols = ds.fact_columns(i, mod.COLUMNS)
        for d, dv, it, p, pv in zip(
                cols["ss_sold_date_sk"][0].tolist(),
                cols["ss_sold_date_sk"][1].tolist(),
                cols["ss_item_sk"][0].tolist(),
                cols["ss_ext_sales_price"][0].tolist(),
                cols["ss_ext_sales_price"][1].tolist()):
            if not (dv and pv) or moy_of.get(d) != 11 \
                    or item["i_manufact_id"][it - 1] != 128:
                continue
            key = (year_of[d], int(item["i_brand_id"][it - 1]))
            sums[key] = sums.get(key, 0) + p
    want = sorted(((y, b, mod.brand_name(b - 1001001), s)
                   for (y, b), s in sums.items()),
                  key=lambda r: (r[0], -r[3], r[1]))[:100]
    parts = [mod.reference_partial(ds.fact_columns(i, mod.COLUMNS), c.look)
             for i in (0, 3)]
    got = mod.answer(parts[0][0] + parts[1][0], parts[0][1] + parts[1][1],
                     c.look)
    assert want and got == want


def test_the_runs_are_the_lookup_for_every_key(consumer):
    """The step's join (a key against the runs of its lookup) gives the
    lookup array's slot for every key, in range or not."""
    import jax
    import jax.numpy as jnp

    mod, c = consumer
    rng = np.random.default_rng(5)
    for lut, base in ((c.look.date, c.look.date_base),
                      (c.look.item, c.look.item_base)):
        keys = np.concatenate([np.arange(base - 3, base + len(lut) + 3),
                               rng.integers(-2**31, 2**31 - 1, 1000)])
        lo, hi, slot = mod.runs(lut, base)
        got = jax.jit(lambda k: jnp.max(jnp.where(
            (k[:, None] >= lo) & (k[:, None] <= hi), slot, -1), axis=1))(
                keys.astype(np.int32))
        assert np.array_equal(np.asarray(got), mod._look(lut, keys - base))


def test_the_lookups_from_arrow_equal_the_reference(consumer):
    """The cell's job start hands ``lookups`` the item's brand names as
    Arrow's column, which makes only the qualifying names strings: the
    lookups and the names equal those of the reference's NumPy names."""
    mod, c = consumer
    ds = c.dataset
    item = ds.dim_table("item")
    got = mod.lookups(ds.date_columns(),
                      {k: item.column(k) if k == "i_brand" else
                       item.column(k).to_numpy() for k in item.column_names})
    want = c.reference_lookups()
    assert got.names == want.names and len(got.names) > 100
    for a, b in zip(got, want):
        assert np.array_equal(a, b) if isinstance(a, np.ndarray) else a == b
