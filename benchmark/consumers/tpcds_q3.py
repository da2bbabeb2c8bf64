"""TPC-DS query 3 over ``store_sales`` at scale factor 100, read as a
Presto or Spark worker reads it: Parquet files of one row group, Q3's
three columns fetched by the footer/range planner and landed on the
device through ``DeviceBlockLoader.row_groups``, joined with the two
dimensions and summed there, one jitted program a row group.

Q3 (TPC-DS v2, its qualification values)::

    select d_year, i_brand_id brand_id, i_brand brand,
           sum(ss_ext_sales_price) sum_agg
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manufact_id = 128 and d_moy = 11
    group by d_year, i_brand, i_brand_id
    order by d_year, sum_agg desc, brand_id
    limit 100

The data (:class:`StoreSales`) is drawn from ``--seed``, one stream a
(file, column), as dsdgen derives it (the amounts from quantity,
wholesale cost, markup and discount), every non-key column NULL at 4%;
``date_dim`` is the calendar, ``item`` a seeded draw of its manufacturer
and brand. Each ``dec`` column is ``decimal(7,2)``, stored as its INT32
cents as Spark stores it.

The dimensions become two lookups (:func:`lookups`): date_sk -> year
slot (-1 unless ``d_moy`` = 11) and item_sk -> brand slot (-1 unless
``i_manufact_id`` = 128), on the device as their runs (:func:`runs`). A
step joins both keys of every row of a row group with them, drops NULL
keys and prices, misses and the padding, writes the exact cents sum and
row count of every (year, brand) group into a device slot ring, and adds
them into the pass's running totals on the device: a group's cents and
the whole pass's, int64 numbers that outgrow float32's exact integers
and int32. The groups are every year of ``date_dim`` by every brand of
``item``, and the runs are padded to a fixed length, so the program's
shapes do not follow the seed.

Plain reference, independent of ``alluxio_tpu`` and of Parquet
decoding: each file's needed columns regenerated from the seed, the
lookups built from the generated dimensions, the partial summed with
NumPy in int64. ``check`` holds every consumed step's partial (all
groups, cents and counts) to it, the pass's running totals, Q3's answer
over the files consumed, and one landed row group value for value and
mask for mask."""

from __future__ import annotations

import functools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import NamedTuple

import numpy as np

from benchmark.harness.loader_cell import LoaderCell

#: Q3's projection of store_sales, in schema order
COLUMNS = ("ss_sold_date_sk", "ss_item_sk", "ss_ext_sales_price")
#: a row of the projection at its schema widths: three 4-byte values
ROW_BYTES = 12
#: Q3's qualification values and its LIMIT
MANUFACT_ID = 128
MOY = 11
LIMIT = 100
#: a NULL in one row of 25 (4%) in every nullable column
NULL_ONE_IN = 25
#: line items a ticket (287,997,024 rows over ~24M SF100 tickets)
ITEMS_PER_TICKET = 12
#: store_sales' 23 columns in TPC-DS order: (name, kind, nullable, the
#: column's own uniform draw [lo, hi] or None where dsdgen derives it).
#: ``dec`` = decimal(7,2) in cents. The draws of ss_list_price,
#: ss_sales_price, ss_ext_tax and ss_coupon_amt are a markup %, a
#: discount %, a tax in basis points and a coupon % (over 100: none)
FACT = (
    ("ss_sold_date_sk", "int32", True, (2450816, 2452642)),
    ("ss_sold_time_sk", "int32", True, (28800, 75599)),
    ("ss_item_sk", "int32", False, (1, 204000)),
    ("ss_customer_sk", "int32", True, (1, 2000000)),
    ("ss_cdemo_sk", "int32", True, (1, 1920800)),
    ("ss_hdemo_sk", "int32", True, (1, 7200)),
    ("ss_addr_sk", "int32", True, (1, 1000000)),
    ("ss_store_sk", "int32", True, (1, 402)),
    ("ss_promo_sk", "int32", True, (1, 1000)),
    ("ss_ticket_number", "int64", False, None),
    ("ss_quantity", "int32", True, (1, 100)),
    ("ss_wholesale_cost", "dec", True, (100, 10000)),
    ("ss_list_price", "dec", True, (0, 200)),
    ("ss_sales_price", "dec", True, (0, 100)),
    ("ss_ext_discount_amt", "dec", True, None),
    ("ss_ext_sales_price", "dec", True, None),
    ("ss_ext_wholesale_cost", "dec", True, None),
    ("ss_ext_list_price", "dec", True, None),
    ("ss_ext_tax", "dec", True, (0, 900)),
    ("ss_coupon_amt", "dec", True, (0, 500)),
    ("ss_net_paid", "dec", True, None),
    ("ss_net_paid_inc_tax", "dec", True, None),
    ("ss_net_profit", "dec", True, None),
)
_INDEX = {name: c for c, (name, *_rest) in enumerate(FACT)}
#: date_dim: 1900-01-02 (Julian day 2,415,022) on, 73,049 days
DATE_FIRST = "1900-01-02"
DATE_FIRST_SK = 2415022
DATE_ROWS = 73049
#: item at SF100, and the ids its brand and manufacturer are drawn from
ITEM_ROWS = 204000
BRANDS = 1000
MANUFACTS = 1000
#: dsdgen's syllables, which spell a brand's name from its digits
SYLLABLES = ("ought", "able", "pri", "ese", "anti", "cally", "ation",
             "eing", "n st", "bar")
#: a lookup's runs are padded to a multiple of this (a dimension has
#: ~200 at SF100), so one program serves every seed. Not a multiple of
#: 128: on a TPU v5e a compare of 2 Mi keys with 256 runs takes 2.36 ms,
#: with 248 or 264 runs 1.30-1.40
RUNS_PAD = 264
#: the dtype the step sums cents in, a row group's and the pass's. A
#: group's total over a pass outgrows float32's exact integers (2**24)
#: and the pass's total int32 (2**31): only int64 holds them exactly
ACC = "int64"


def _rng(seed: int, *stream: int):
    return np.random.default_rng([int(seed), *stream])


def _arrow_column(kind: str, values: np.ndarray, valid):
    """A pyarrow array over the NumPy buffers, no Python objects: a
    ``dec`` as ``decimal32(7,2)``, which Parquet stores as INT32."""
    import pyarrow as pa

    typ = {"int32": pa.int32(), "int64": pa.int64(),
           "dec": pa.decimal32(7, 2)}[kind]
    n = len(values)
    bitmap = None if valid is None else \
        pa.py_buffer(np.packbits(valid, bitorder="little"))
    nulls = 0 if valid is None else int(n - np.count_nonzero(valid))
    return pa.Array.from_buffers(
        typ, n, [bitmap, pa.py_buffer(values)], null_count=nulls)


class StoreSales:
    """The fact files, ``rows_per_file`` rows each (one row group) in
    generation order, then ``date_dim`` and ``item``, one file each.
    What ``bdata.ingest`` asks of a set: ``n_files``, ``file_bytes`` (the
    largest a file may encode to: one block), ``paths``,
    ``total_bytes``, ``file(i, out=)`` (a view of ``out``)."""

    def __init__(self, seed: int, *, rows: int, rows_per_file: int,
                 file_bytes: int, prefix: str = "/bench/tpcds") -> None:
        self.seed = seed
        self.rows = rows
        self.rows_per_file = rows_per_file
        self.n_fact = -(-rows // rows_per_file)
        self.fact_paths = [f"{prefix}/store_sales/part-{i:05d}.parquet"
                           for i in range(self.n_fact)]
        self.date_path = f"{prefix}/date_dim/part-00000.parquet"
        self.item_path = f"{prefix}/item/part-00000.parquet"
        self.paths = self.fact_paths + [self.date_path, self.item_path]
        self.n_files = len(self.paths)
        self.file_bytes = file_bytes
        self._sizes: dict = {}

    @property
    def total_bytes(self) -> int:
        """The encoded bytes of every file written so far."""
        return sum(self._sizes.values())

    def file_rows(self, i: int) -> int:
        return min(self.rows_per_file, self.rows - i * self.rows_per_file)

    # -- the fact table ----------------------------------------------------
    def fact_columns(self, i: int, names=None) -> dict:
        """``{name: (values, valid or None)}`` of fact file ``i`` (all
        23 where ``names`` is None), values in each column's own dtype
        (cents for a ``dec``), a NULL's value as drawn."""
        names = [f[0] for f in FACT] if names is None else list(names)
        n = self.file_rows(i)
        drawn: dict = {}

        def draw(name: str):
            """(values, valid) of a column with a draw of its own: the
            draw first, then the NULLs, from the column's stream."""
            if name not in drawn:
                c = _INDEX[name]
                _n, _kind, nullable, (lo, hi) = FACT[c]
                rng = _rng(self.seed, i, c)
                vals = rng.integers(lo, hi + 1, size=n, dtype=np.int32)
                drawn[name] = (vals, self._valid(rng, n, nullable))
            return drawn[name]

        def derived(name: str):
            c = _INDEX[name]
            return self._valid(_rng(self.seed, i, c), n, FACT[c][2])

        priced = {f[0] for f in FACT if f[1] == "dec"} - {
            "ss_wholesale_cost"}
        amounts = self._amounts(draw) if priced.intersection(names) else {}
        out = {}
        for name in names:
            c = _INDEX[name]
            _n, kind, _nullable, spec = FACT[c]
            if name == "ss_ticket_number":
                first = i * self.rows_per_file
                out[name] = (np.arange(first, first + n, dtype=np.int64)
                             // ITEMS_PER_TICKET + 1, None)
            elif name in amounts:
                valid = drawn[name][1] if spec is not None \
                    else derived(name)
                out[name] = (amounts[name], valid)
            else:
                out[name] = draw(name)
        return out

    @staticmethod
    def _valid(rng, n: int, nullable: bool):
        if not nullable:
            return None
        return rng.integers(0, NULL_ONE_IN, size=n, dtype=np.uint8) != 0

    @staticmethod
    def _amounts(draw) -> dict:
        """dsdgen's pricing of a line item, in integer cents: list =
        wholesale x (1 + markup), sales = list x (1 - discount), the
        extended amounts x quantity, a coupon off the sale, tax on what
        is paid (each product truncated to the cent)."""
        q = draw("ss_quantity")[0].astype(np.int64)
        whole = draw("ss_wholesale_cost")[0].astype(np.int64)
        lst = whole * (100 + draw("ss_list_price")[0]) // 100
        sale = lst * (100 - draw("ss_sales_price")[0]) // 100
        coupon_pct = draw("ss_coupon_amt")[0].astype(np.int64)
        ext_sales = q * sale
        coupon = ext_sales * np.where(coupon_pct <= 100, coupon_pct, 0) \
            // 100
        paid = ext_sales - coupon
        tax = paid * draw("ss_ext_tax")[0] // 10000
        ext_whole = q * whole
        got = {
            "ss_wholesale_cost": whole, "ss_list_price": lst,
            "ss_sales_price": sale, "ss_ext_discount_amt": q * (lst - sale),
            "ss_ext_sales_price": ext_sales,
            "ss_ext_wholesale_cost": ext_whole, "ss_ext_list_price": q * lst,
            "ss_ext_tax": tax, "ss_coupon_amt": coupon, "ss_net_paid": paid,
            "ss_net_paid_inc_tax": paid + tax,
            "ss_net_profit": paid - ext_whole}
        return {k: v.astype(np.int32) for k, v in got.items()}

    def fact_table(self, i: int):
        import pyarrow as pa

        cols = self.fact_columns(i)
        fields, arrays = [], []
        for name, kind, nullable, _spec in FACT:
            values, valid = cols[name]
            arrays.append(_arrow_column(kind, values, valid))
            fields.append(pa.field(name, arrays[-1].type, nullable=nullable))
        return pa.Table.from_arrays(arrays, schema=pa.schema(fields))

    # -- the dimensions ----------------------------------------------------
    def date_columns(self) -> dict:
        days = np.datetime64(DATE_FIRST) + np.arange(DATE_ROWS)
        years = days.astype("datetime64[Y]").astype(np.int64) + 1970
        months = days.astype("datetime64[M]").astype(np.int64) % 12 + 1
        return {"d_date_sk": np.arange(DATE_FIRST_SK,
                                       DATE_FIRST_SK + DATE_ROWS,
                                       dtype=np.int32),
                "d_year": years.astype(np.int32),
                "d_moy": months.astype(np.int32)}

    def item_columns(self) -> dict:
        c = len(self.fact_paths) + 1  # the item file's streams
        brand = _rng(self.seed, c, 0).integers(0, BRANDS, size=ITEM_ROWS)
        manufact = _rng(self.seed, c, 1).integers(
            1, MANUFACTS + 1, size=ITEM_ROWS, dtype=np.int32)
        return {"i_item_sk": np.arange(1, ITEM_ROWS + 1, dtype=np.int32),
                "i_brand_id": brand_id(brand),
                "i_brand_index": brand,
                "i_manufact_id": manufact}

    def dim_table(self, which: str):
        import pyarrow as pa

        if which == "date":
            return pa.table(self.date_columns())
        cols = self.item_columns()
        names = pa.array([brand_name(b) for b in range(BRANDS)])
        return pa.table({
            "i_item_sk": cols["i_item_sk"], "i_brand_id": cols["i_brand_id"],
            "i_brand": names.take(pa.array(cols["i_brand_index"])),
            "i_manufact_id": cols["i_manufact_id"]})

    # -- the files as written ----------------------------------------------
    def file(self, i: int, out=None) -> np.ndarray:
        """File ``i`` encoded as Spark writes Parquet (snappy, dictionary
        on, 1 MiB pages, decimals as INT32, one row group; no Arrow
        schema in the footer): a view of ``out``."""
        import pyarrow as pa
        import pyarrow.parquet as pq

        if out is None:
            out = np.empty(self.file_bytes, np.uint8)
        if i < self.n_fact:
            table = self.fact_table(i)
        else:
            table = self.dim_table("date" if i == self.n_fact else "item")
        sink = pa.FixedSizeBufferWriter(pa.py_buffer(out))
        with pq.ParquetWriter(sink, table.schema, compression="snappy",
                              use_dictionary=True, data_page_size=1 << 20,
                              store_decimal_as_integer=True,
                              store_schema=False) as w:
            w.write_table(table, row_group_size=max(1, table.num_rows))
        size = sink.tell()
        self._sizes[i] = size
        return out[:size]


def brand_id(index):
    """The ``i_brand_id`` of brand ``index`` (0-999)."""
    return (1001001 + np.asarray(index)).astype(np.int32)


def brand_name(index: int) -> str:
    """dsdgen's way: a syllable a digit of the brand's number."""
    return "".join(SYLLABLES[int(d)] for d in f"{index:03d}")


# -- the join's lookups --------------------------------------------------------
class Lookups(NamedTuple):
    date_base: int       # d_date_sk of slot 0 of ``date``
    date: np.ndarray     # int32: date_sk - date_base -> year slot, or -1
    first_year: int      # the year of year slot 0
    n_years: int
    item_base: int
    item: np.ndarray     # int32: item_sk - item_base -> brand slot, or -1
    brands: np.ndarray   # i_brand_id of each brand slot, ascending
    names: dict          # i_brand_id -> i_brand, of the brands in a slot

    @property
    def n_groups(self) -> int:
        return self.n_years * len(self.brands)


def lookups(date: dict, item: dict) -> Lookups:
    """Q3's qualification as two lookup arrays, from the dimensions'
    columns (NumPy arrays; ``item`` with ``i_brand`` as a list, a NumPy
    or a pyarrow array of names): a year slot for every November day, a brand slot for
    every item of manufacturer 128. The slots are every year of
    ``date_dim`` and every brand id of ``item``, in ascending order,
    whether a group can be reached or not."""
    sk, year, moy = (np.asarray(date[k]) for k in
                     ("d_date_sk", "d_year", "d_moy"))
    first_year = int(year.min())
    date_base = int(sk.min())
    date_lut = np.full(int(sk.max()) - date_base + 1, -1, np.int32)
    date_lut[sk - date_base] = np.where(moy == MOY, year - first_year, -1)
    isk, bid = np.asarray(item["i_item_sk"]), np.asarray(item["i_brand_id"])
    qual = np.asarray(item["i_manufact_id"]) == MANUFACT_ID
    # the brand ids present, by a count (a sort of 204,000 ids would lie
    # in every job start); only a qualifying brand can reach the answer
    low = int(bid.min())
    brands = (np.flatnonzero(np.bincount(bid - low)) + low).astype(bid.dtype)
    item_base = int(isk.min())
    item_lut = np.full(int(isk.max()) - item_base + 1, -1, np.int32)
    item_lut[isk - item_base] = np.where(
        qual, np.searchsorted(brands, bid), -1)
    names = dict(zip(bid[qual].tolist(), _where(item["i_brand"], qual)))
    return Lookups(date_base, date_lut, first_year,
                   int(year.max()) - first_year + 1, item_base, item_lut,
                   brands, names)


def _where(names, mask: np.ndarray) -> list:
    """The entries of ``names`` where ``mask`` holds. A pyarrow array is
    filtered by Arrow, so only the ~200 qualifying names become Python
    strings, not all 204,000 of ``item``."""
    if hasattr(names, "filter"):
        import pyarrow as pa

        return names.filter(pa.array(mask)).to_pylist()
    return list(np.asarray(names, dtype=object)[mask])


def _look(lut: np.ndarray, keys: np.ndarray) -> np.ndarray:
    idx = keys.astype(np.int64)
    inside = (idx >= 0) & (idx < len(lut))
    return np.where(inside, lut[np.clip(idx, 0, len(lut) - 1)], -1)


def reference_partial(cols: dict, look: Lookups):
    """``(sums, counts)``, int64 a group, of one row group's rows."""
    d, dv = cols["ss_sold_date_sk"]
    item = cols["ss_item_sk"][0]
    price, pv = cols["ss_ext_sales_price"]
    ys = _look(look.date, d - look.date_base)
    bs = _look(look.item, item - look.item_base)
    hit = dv & pv & (ys >= 0) & (bs >= 0)
    g = ys[hit] * len(look.brands) + bs[hit]
    sums = np.zeros(look.n_groups, np.int64)
    np.add.at(sums, g, price[hit].astype(np.int64))
    return sums, np.bincount(g, minlength=look.n_groups).astype(np.int64)


def answer(sums: np.ndarray, counts: np.ndarray, look: Lookups) -> list:
    """Q3's rows ``(d_year, brand_id, brand, sum_agg)`` of the summed
    partials: ORDER BY d_year, sum_agg DESC, brand_id, LIMIT 100."""
    rows = []
    nb = len(look.brands)
    for g in np.flatnonzero(counts):
        y, b = divmod(int(g), nb)
        bid = int(look.brands[b])
        rows.append((look.first_year + y, bid, look.names[bid],
                     int(sums[g])))
    rows.sort(key=lambda r: (r[0], -r[3], r[1]))
    return rows[:LIMIT]


def step_bytes_needed(rows: int) -> int:
    """Bytes a lookup join of one row group moves from HBM, from shapes:
    the landed values (three 4-B columns) and masks (two 1-B) read once,
    and one 4-B lookup entry gathered a key a row. The step as written
    gathers nothing (it compares each key with the runs of its lookup),
    so it reads 14 of these 22 B a row: this is the lookup-join model."""
    return rows * (3 * 4 + 2 * 1 + 2 * 4)


def runs(lut: np.ndarray, base: int) -> tuple:
    """A lookup array as its maximal runs of one slot >= 0: ``(lo, hi,
    slot)`` int32 arrays of keys, ``lo <= key <= hi`` -> ``slot``, the
    same function of every key (the rest is -1); padded with runs that
    hold no key (1, 0, -1) to a multiple of ``RUNS_PAD``."""
    starts = np.concatenate([[0], np.flatnonzero(np.diff(lut)) + 1])
    ends = np.append(starts[1:], len(lut)) - 1
    keep = lut[starts] >= 0
    n = int(np.count_nonzero(keep))
    pad = max(1, -(-n // RUNS_PAD)) * RUNS_PAD - n
    return (np.append(starts[keep] + base, [1] * pad).astype(np.int32),
            np.append(ends[keep] + base, [0] * pad).astype(np.int32),
            np.append(lut[starts[keep]], [-1] * pad).astype(np.int32))


def device_lookups(look: Lookups) -> tuple:
    """What the step joins with: both lookups as their runs (November
    of each year; each item of manufacturer 128)."""
    return (runs(look.date, look.date_base), runs(look.item, look.item_base))


def new_state(ring_steps: int, n_groups: int, device, acc: str = ACC):
    """The step's state, zeroed on ``device`` (under
    ``jax.enable_x64``): the slot ring of ``ring_steps`` + 1 partials
    (the last slot the cold starts'), sums as (low uint32, high int32)
    words and counts int32; and the pass's running totals, a group's
    cents (``acc``) and rows (int32) and the whole pass's cents
    (``acc``)."""
    import jax.numpy as jnp

    shape = (ring_steps + 1, n_groups)

    def zeros(s, dtype):
        return jnp.zeros(s, dtype, device=device)

    return ((zeros(shape, jnp.uint32), zeros(shape, jnp.int32),
             zeros(shape, jnp.int32)),
            (zeros(n_groups, acc), zeros(n_groups, jnp.int32),
             zeros((), acc)))


def make_step(rows: int, look: Lookups, ring_steps: int, *,
              acc: str = ACC, block: int = 4096):
    """One jitted program a row group. The join compares every key with
    the runs of its lookup (a few hundred a dimension, no gather of a
    row's key: on a TPU v5e a gather or scatter of 2 Mi rows costs
    15-130 ms, a compare of each row with 400 bounds about a
    millisecond); the
    rows that land in a group are then taken ``block`` at a time, the
    k-th found by a binary search of the running count of hits, and
    only they are scattered into the (year, brand) groups, summed in
    ``acc``. A step into a slot of the ring (``slot < ring_steps``)
    also adds its partial into the pass's totals; a cold start's (slot
    ``ring_steps``) does not."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    n_brands, n_groups = len(look.brands), look.n_groups
    acc = jnp.dtype(acc)

    def lookup(keys, lo, hi, slot):
        inside = (keys[:, None] >= lo) & (keys[:, None] <= hi)
        return jnp.max(jnp.where(inside, slot, -1), axis=1)

    @functools.partial(jax.jit, donate_argnums=(3,))
    def bench_q3_partial(rg, date_runs, item_runs, state, slot):
        price = rg.values["ss_ext_sales_price"]
        ys = lookup(rg.values["ss_sold_date_sk"], *date_runs)
        bs = lookup(rg.values["ss_item_sk"], *item_runs)
        hit = ((jnp.arange(rows, dtype=jnp.int32) < rg.num_rows)
               & rg.valid["ss_sold_date_sk"] & rg.valid["ss_ext_sales_price"]
               & (ys >= 0) & (bs >= 0))
        group = ys * n_brands + bs
        ranks = jnp.cumsum(hit, dtype=jnp.int32)

        def add_block(i, carry):
            part, count = carry
            k = i * block + jnp.arange(block, dtype=jnp.int32)
            row = jnp.clip(jnp.searchsorted(ranks, k + 1), 0, rows - 1)
            # past the last hit: slot n_groups, which the scatter drops
            g = jnp.where(k < ranks[-1], group[row], n_groups)
            return (part.at[g].add(price[row].astype(acc), mode="drop"),
                    count.at[g].add(jnp.int32(1), mode="drop"))

        part, count = lax.fori_loop(
            0, (ranks[-1] + block - 1) // block, add_block,
            (jnp.zeros(n_groups, acc), jnp.zeros(n_groups, jnp.int32)))
        # the ring keeps a sum as its two 32-bit words: an int64 ring is
        # split into halves and joined again whole on every update here
        (lo, hi, counts), (sums, tally, whole) = state
        cents = part.astype(jnp.int64)
        in_pass = slot < ring_steps
        return ((lo.at[slot].set(cents.astype(jnp.uint32)),
                 hi.at[slot].set((cents >> 32).astype(jnp.int32)),
                 counts.at[slot].set(count)),
                (sums + jnp.where(in_pass, part, 0),
                 tally + jnp.where(in_pass, count, 0),
                 whole + jnp.where(in_pass, part.sum(dtype=acc), 0))), count

    return bench_q3_partial


class Consumer(LoaderCell):
    def __init__(self, **kw) -> None:
        super().__init__(**kw)
        from alluxio_tpu.client.jax_io import DeviceBlockLoader

        if not hasattr(DeviceBlockLoader, "row_groups"):
            # before the ingest: a program that lands no column cannot
            # run this cell, and says so at once
            raise SystemExit(
                "DeviceBlockLoader has no row_groups(): this program "
                "lands no Parquet projection on the device")
        config, traffic = self.config, self.traffic
        self.dataset = StoreSales(
            self.seed, rows=config["rows"],
            rows_per_file=config["rows_per_file"],
            file_bytes=config["block_bytes"])
        if self.dataset.n_fact != traffic["files"]:
            raise SystemExit(
                f"the configuration's {config['rows']} rows make "
                f"{self.dataset.n_fact} files of {config['rows_per_file']}; "
                f"the traffic file reads {traffic['files']}")
        self.warm_items = traffic["warm_row_groups"]
        #: steps the device's slot ring holds; one more slot takes the
        #: cold starts' partials
        self.ring = traffic["ring_steps"]
        self.rows = config["rows_per_file"]
        self.step_bytes_needed = step_bytes_needed(self.rows)
        self.n_steps = 0
        self._kept = None  # (step, RowGroup): the first of the window

    def new_loader(self, fs, paths=None, *, hbm_bytes=None):
        return super().new_loader(
            fs, self.dataset.fact_paths if paths is None else paths,
            hbm_bytes=hbm_bytes)

    def items(self, loader):
        return loader.row_groups(COLUMNS, rows=self.rows)

    def _lookups(self, fs):
        """Both dimensions read through ``read_columns`` (the planned
        host path) and their lookups put on the device."""
        import jax

        from alluxio_tpu.table.reader import read_columns

        date = read_columns(fs, [self.dataset.date_path],
                            ["d_date_sk", "d_year", "d_moy"])
        item = read_columns(fs, [self.dataset.item_path],
                            ["i_item_sk", "i_brand_id", "i_brand",
                             "i_manufact_id"])
        look = lookups(
            {k: date.column(k).to_numpy() for k in date.column_names},
            {k: item.column(k) if k == "i_brand" else
             item.column(k).to_numpy() for k in item.column_names})
        return look, jax.device_put(device_lookups(look), self.device)

    def open(self, fs) -> None:
        import jax

        super().open(fs)
        self.look, self._luts = self._lookups(fs)
        self._step = make_step(self.rows, self.look, self.ring, acc=ACC)
        #: (the slot ring of every step's partial, the pass's totals)
        with jax.enable_x64(True):
            self._state = new_state(self.ring, self.look.n_groups,
                                    self.device, acc=ACC)

    def _run(self, rg, luts, slot: int):
        import jax

        with jax.enable_x64(True):
            self._state, token = self._step(rg, *luts, self._state,
                                            np.int32(slot))
        return token

    def step(self, rg):
        if self.n_steps == self.warm_items:
            self._kept = (self.n_steps, rg)
        token = self._run(rg, self._luts, self.n_steps % self.ring)
        file_i = self.n_steps % self.dataset.n_fact
        self.n_steps += 1
        return token, ROW_BYTES * self.dataset.file_rows(file_i)

    def cold_start(self) -> dict:
        """A new job: the planner's caches empty, the collector's garbage
        collected and the pages Arrow's pool keeps freed given back (a new
        process has none of them: without the last two, what the window
        and the starts before left decides how many pages a start's
        decode and landing must fault in, and the starts speed up as it
        drains), then new client, both dimensions read and their lookups
        on the device, a new loader over the fact files (one
        ``get_status_many``), the first row group's partial through
        ``block_until_ready``."""
        import gc

        import jax
        import pyarrow as pa

        from alluxio_tpu.table import plan

        plan.clear_caches()
        gc.collect()
        pa.default_memory_pool().release_unused()
        t0 = time.perf_counter()
        fs = self.roles.file_system()
        look, luts = self._lookups(fs)
        if jax.tree.map(np.shape, luts) != jax.tree.map(np.shape, self._luts):
            raise AssertionError("a cold start's lookups differ from the "
                                 "window's")
        t1 = time.perf_counter()
        loader = self.new_loader(fs)
        t2 = time.perf_counter()
        it = self.items(loader)
        try:
            self._run(next(it), luts, self.ring).block_until_ready()
            t3 = time.perf_counter()
        finally:
            it.close()
        status_ms = []
        for path in self.dataset.fact_paths[:16]:
            s0 = time.perf_counter()
            fs.get_status(path)
            status_ms.append((time.perf_counter() - s0) * 1e3)
        loader.close()
        fs.close()
        return {"first_batch_ms": (t3 - t0) * 1e3,
                "loader_ctor_ms": (t2 - t1) * 1e3,
                "get_status_ms": status_ms}

    # -- against the plain reference --------------------------------------------
    def reference_lookups(self) -> Lookups:
        item = self.dataset.item_columns()
        names = np.array([brand_name(b) for b in range(BRANDS)], object)
        item["i_brand"] = names[item["i_brand_index"]]
        return lookups(self.dataset.date_columns(), item)

    def check(self) -> dict:
        ds, n = self.dataset, self.n_steps
        look = self.reference_lookups()
        first = max(0, n - self.ring)
        steps = list(range(first, n))
        # how often the pass consumed each file: its running totals
        times = np.bincount(np.arange(n) % ds.n_fact, minlength=ds.n_fact)
        files = np.flatnonzero(times).tolist()
        lock = threading.Lock()
        want = {}

        def ref(i):
            got = reference_partial(ds.fact_columns(i, COLUMNS), look)
            with lock:
                want[i] = got

        with ThreadPoolExecutor(8) as pool:
            list(pool.map(ref, files))
        ring, (sums, tally, whole) = self._state
        lo, hi, counts = (np.asarray(a) for a in ring)

        def ring_sums(r):
            return (hi[r].astype(np.int64) << 32) + lo[r].astype(np.int64)

        bad = [s for s in steps
               if not (np.array_equal(ring_sums(s % self.ring),
                                      want[s % ds.n_fact][0])
                       and np.array_equal(counts[s % self.ring],
                                          want[s % ds.n_fact][1]))]
        failed = len(bad)
        # the pass's totals, summed on the device in ACC, against the
        # reference's in int64: 32 bits do not hold them exactly
        want_sums = np.zeros(look.n_groups, np.int64)
        want_tally = np.zeros(look.n_groups, np.int64)
        for i in files:
            want_sums += times[i] * want[i][0]
            want_tally += times[i] * want[i][1]
        got_sums, got_whole = np.asarray(sums), np.asarray(whole)
        same_totals = bool(
            np.array_equal(got_sums, want_sums)
            and np.array_equal(np.asarray(tally), want_tally)
            and got_whole == int(want_sums.sum()))
        failed += 0 if same_totals else 1
        # Q3 over the files consumed, each once: the device's partials
        # (the first step of each file in the ring) against the seed's
        seen = {}
        for s in steps:
            seen.setdefault(s % ds.n_fact, s % self.ring)
        got_ans = answer(sum(ring_sums(r) for r in seen.values()),
                         sum(counts[r] for r in seen.values()), look) \
            if seen else []
        want_ans = answer(sum(want[i][0] for i in seen),
                          sum(want[i][1] for i in seen), look) \
            if seen else []
        same_answer = got_ans == want_ans
        failed += 0 if same_answer else 1
        notes = {"steps": n, "steps_checked": len(steps),
                 "files_checked": len(files), "groups": look.n_groups,
                 "rows_in_groups": int(sum(want[i][1].sum() for i in seen)),
                 "answer_rows": len(want_ans), "answer_equal": same_answer,
                 "answer_head": [list(r) for r in want_ans[:3]],
                 "totals_equal": same_totals,
                 "pass_cents": int(want_sums.sum()),
                 "group_cents_max": int(want_sums.max()),
                 "totals_off": int(np.count_nonzero(got_sums != want_sums)),
                 "first_mismatches": bad[:8]}
        if self._kept is not None:
            s, rg = self._kept
            same = self.landed_equal(rg, s % ds.n_fact)
            notes["row_group_equal"] = [s, same]
            failed += 0 if same else 1
        return {"failed": failed, "notes": notes}

    def landed_equal(self, rg, i: int) -> bool:
        """One landed row group against fact file ``i`` regenerated:
        every value (0 at a NULL and in the padding), every mask, the
        row count."""
        cols = self.dataset.fact_columns(i, COLUMNS)
        n = self.dataset.file_rows(i)
        if int(np.asarray(rg.num_rows)) != n:
            return False
        for name in COLUMNS:
            vals, valid = cols[name]
            want = np.zeros(self.rows, np.int32)
            want[:n] = vals if valid is None else np.where(valid, vals, 0)
            if not np.array_equal(np.asarray(rg.values[name]), want):
                return False
            if valid is None:
                if name in rg.valid:
                    return False
                continue
            mask = np.zeros(self.rows, bool)
            mask[:n] = valid
            if not np.array_equal(np.asarray(rg.valid[name]), mask):
                return False
        return True
