"""TPC-C traffic (TPC-C v5.11) over a key-value store, one transaction
per session.

Started from the repository's ``benchmarks.workloads.TPCC`` generator and
brought to the specification's shapes: the cardinalities of §4.3.3.1,
the row widths of §1.3, 5-15 order lines per order, NURand (§2.1.6) for
customer ids, last names and item ids, customers chosen by last name 60%
of the time, and the standard mix of §5.2.3.  Each row is one container
``(table, row id, column)``; an order line is the column ``l<n>`` of its
order's row, as in the repository's generator.

Values identify their key and the write that made them: 4 bytes of the
key's CRC-32, 4 bytes of the write's sequence number (0 at load), then
filler from the seed up to the table's row width.  So a stale or a wrong
read shows.

``build(config, mix, seed)`` returns the data set, the backlog and the
window's transactions; an operation is ``(key, None)`` for a read and
``(key, value)`` for a write.  The transactions are drawn from the mix's
``structure_seed``; ``seed`` renames the districts, customers and items
(a permutation each) and draws the values.  So every seed mines the same
sizes and compiles the same programs, while no two seeds touch the same
rows.
"""

from __future__ import annotations

import collections
import struct
import time
import zlib

import numpy as np

MIX = (("new_order", 0.45), ("payment", 0.43), ("order_status", 0.04),
       ("delivery", 0.04), ("stock_level", 0.04))


def k_warehouse(w):
    return ("warehouse", f"w{w}", "info")


def k_district(w, d):
    return ("district", f"w{w}d{d}", "info")


def k_customer(w, d, c):
    return ("customer", f"w{w}d{d}c{c}", "info")


def k_history(w, d, c, h):
    return ("history", f"w{w}d{d}c{c}h{h}", "info")


def k_new_order(w, d, o):
    return ("new_order", f"w{w}d{d}o{o}", "info")


def k_order(w, d, o):
    return ("orders", f"w{w}d{d}o{o}", "info")


def k_order_line(w, d, o, line):
    return ("order_line", f"w{w}d{d}o{o}", f"l{line}")


def k_item(i):
    return ("item", f"i{i}", "info")


def k_stock(w, i):
    return ("stock", f"w{w}i{i}", "qty")


class Values:
    """Row values of the widths of §1.3, each naming its key and write."""

    def __init__(self, widths: dict, seed: int):
        self.widths = widths
        self.filler = np.random.default_rng([seed, 2]).integers(
            0, 256, max(widths.values()), dtype=np.uint8).tobytes()

    def __call__(self, key: tuple, seq: int) -> bytes:
        head = struct.pack("<II", zlib.crc32(repr(key).encode()), seq)
        return (head + self.filler)[:self.widths[key[0]]]


class Names:
    """The rows' names under ``seed``: districts, customers and items
    renamed by a permutation each (``None``: the identity)."""

    def __init__(self, data: dict, seed):
        sizes = (data["districts"], data["customers_per_district"],
                 data["items"])
        if seed is None:
            self.d, self.c, self.i = (np.arange(1, n + 1) for n in sizes)
        else:
            rng = np.random.default_rng(seed)
            self.d, self.c, self.i = (rng.permutation(n) + 1 for n in sizes)
        self.d, self.c, self.i = (a.tolist() for a in (self.d, self.c, self.i))

    def district(self, w, d):
        return k_district(w, self.d[d - 1])

    def customer(self, w, d, c):
        return k_customer(w, self.d[d - 1], self.c[c - 1])

    def history(self, w, d, c, h):
        return k_history(w, self.d[d - 1], self.c[c - 1], h)

    def new_order(self, w, d, o):
        return k_new_order(w, self.d[d - 1], o)

    def order(self, w, d, o):
        return k_order(w, self.d[d - 1], o)

    def order_line(self, w, d, o, line):
        return k_order_line(w, self.d[d - 1], o, line)

    def item(self, i):
        return k_item(self.i[i - 1])

    def stock(self, w, i):
        return k_stock(w, self.i[i - 1])


class TPCC:
    def __init__(self, data: dict, rng: np.random.Generator,
                 values: Values, names: Names | None = None):
        self.d = data
        self.rng = rng
        self.values = values
        self.n = names or Names(data, None)
        self.w = 1
        nd, nc, no = (data["districts"], data["customers_per_district"],
                      data["orders_per_district"])
        # NURand's run constants C (§2.1.6), drawn once
        self.c_last, self.c_id, self.c_item = (
            int(rng.integers(0, a + 1)) for a in (255, 1023, 8191))
        # last names (§4.3.3.1): the first 1000 customers take names 0-999
        # in turn, the rest NURand(255, 0, 999)
        self.by_name = []
        for _ in range(nd):
            names = collections.defaultdict(list)
            for c in range(1, nc + 1):
                n = c - 1 if c <= 1000 else self.nurand(255, 0, 999,
                                                        self.c_last)
                names[n].append(c)
            self.by_name.append(names)
        # the initial orders: o_c_id a permutation of the customers, 5-15
        # lines of uniform items, the last 900 undelivered (new_order)
        self.order_cust = [dict(zip(range(1, no + 1),
                                    (rng.permutation(nc) + 1).tolist()))
                           for _ in range(nd)]
        self.lines = [{o: rng.integers(1, data["items"] + 1,
                                       int(rng.integers(5, 16))).tolist()
                       for o in range(1, no + 1)} for _ in range(nd)]
        self.last_order = [{c: o for o, c in oc.items()}
                           for oc in self.order_cust]
        first_new = no - data["new_orders_per_district"] + 1
        self.undelivered = [collections.deque(range(first_new, no + 1))
                            for _ in range(nd)]
        self.next_o = [no + 1] * nd
        self.history = 0
        self.seq = 0

    def nurand(self, a: int, x: int, y: int, c: int) -> int:
        r = self.rng
        return (((int(r.integers(0, a + 1)) | int(r.integers(x, y + 1)))
                 + c) % (y - x + 1)) + x

    # -- the data set at load (§4.3.3.1) --------------------------------
    def dataset(self) -> dict:
        d, v, w = self.d, self.values, self.w
        out = {}

        def put(k):
            out[k] = v(k, 0)

        put(k_warehouse(w))
        for i in range(1, d["items"] + 1):
            put(self.n.item(i))
            put(self.n.stock(w, i))
        for di in range(d["districts"]):
            dd = di + 1
            put(self.n.district(w, dd))
            for c in range(1, d["customers_per_district"] + 1):
                put(self.n.customer(w, dd, c))
                put(self.n.history(w, dd, c, 0))
            for o, items in self.lines[di].items():
                put(self.n.order(w, dd, o))
                for line in range(1, len(items) + 1):
                    put(self.n.order_line(w, dd, o, line))
            for o in self.undelivered[di]:
                put(self.n.new_order(w, dd, o))
        return out

    # -- transactions as sessions ----------------------------------------
    def _write(self, ops: list, key: tuple) -> None:
        self.seq += 1
        ops.append((key, self.values(key, self.seq)))

    def _customer(self, ops: list, di: int) -> int:
        """60% by last name: every customer of that name is read and the
        middle one taken (§2.5.2.2); else by id, NURand(1023)."""
        if self.rng.random() < 0.6:
            names = self.by_name[di]
            n = self.nurand(255, 0, 999, self.c_last)
            while n not in names:               # only when names are sparse
                n = (n + 1) % 1000
            same = names[n]
            for c in same:
                ops.append((self.n.customer(self.w, di + 1, c), None))
            return same[(len(same) - 1) // 2]
        c = self.nurand(1023, 1, self.d["customers_per_district"], self.c_id)
        ops.append((self.n.customer(self.w, di + 1, c), None))
        return c

    def transaction(self) -> list:
        r = self.rng.random()
        acc, kind = 0.0, MIX[-1][0]
        for name, p in MIX:
            acc += p
            if r < acc:
                kind = name
                break
        return getattr(self, kind)()

    def new_order(self) -> list:
        w, rng = self.w, self.rng
        di = int(rng.integers(0, self.d["districts"]))
        dd = di + 1
        c = self.nurand(1023, 1, self.d["customers_per_district"], self.c_id)
        o = self.next_o[di]
        self.next_o[di] += 1
        items = [self.nurand(8191, 1, self.d["items"], self.c_item)
                 for _ in range(int(rng.integers(5, 16)))]
        ops = [(k_warehouse(w), None), (self.n.district(w, dd), None)]
        self._write(ops, self.n.district(w, dd))
        ops.append((self.n.customer(w, dd, c), None))
        self._write(ops, self.n.order(w, dd, o))
        self._write(ops, self.n.new_order(w, dd, o))
        for line, i in enumerate(items, 1):
            ops += [(self.n.item(i), None), (self.n.stock(w, i), None)]
            self._write(ops, self.n.stock(w, i))
            self._write(ops, self.n.order_line(w, dd, o, line))
        self.order_cust[di][o] = c
        self.lines[di][o] = items
        self.last_order[di][c] = o
        self.undelivered[di].append(o)
        return ops

    def payment(self) -> list:
        w, rng = self.w, self.rng
        di = int(rng.integers(0, self.d["districts"]))
        # 85% the customer's own district; with one warehouse the other
        # 15% pay through another district of it
        cdi = di if rng.random() < 0.85 else int(
            rng.integers(0, self.d["districts"]))
        ops = [(k_warehouse(w), None)]
        self._write(ops, k_warehouse(w))
        ops.append((self.n.district(w, di + 1), None))
        self._write(ops, self.n.district(w, di + 1))
        c = self._customer(ops, cdi)
        self._write(ops, self.n.customer(w, cdi + 1, c))
        self.history += 1
        self._write(ops, self.n.history(w, di + 1, c, self.history))
        return ops

    def order_status(self) -> list:
        w = self.w
        di = int(self.rng.integers(0, self.d["districts"]))
        ops: list = []
        c = self._customer(ops, di)
        o = self.last_order[di].get(c)
        if o is None:           # only at a scale with fewer orders
            return ops
        ops.append((self.n.order(w, di + 1, o), None))
        ops += [(self.n.order_line(w, di + 1, o, line), None)
                for line in range(1, len(self.lines[di][o]) + 1)]
        return ops

    def delivery(self) -> list:
        w = self.w
        ops: list = []
        for di in range(self.d["districts"]):
            if not self.undelivered[di]:
                continue
            dd = di + 1
            o = self.undelivered[di].popleft()
            ops.append((self.n.new_order(w, dd, o), None))
            self._write(ops, self.n.new_order(w, dd, o))
            ops.append((self.n.order(w, dd, o), None))
            self._write(ops, self.n.order(w, dd, o))
            for line in range(1, len(self.lines[di][o]) + 1):
                ops.append((self.n.order_line(w, dd, o, line), None))
                self._write(ops, self.n.order_line(w, dd, o, line))
            c = self.order_cust[di][o]
            ops.append((self.n.customer(w, dd, c), None))
            self._write(ops, self.n.customer(w, dd, c))
        return ops

    def stock_level(self) -> list:
        w = self.w
        di = int(self.rng.integers(0, self.d["districts"]))
        dd = di + 1
        ops = [(self.n.district(w, dd), None)]
        seen: dict = {}
        for o in range(self.next_o[di] - 20, self.next_o[di]):
            for line, i in enumerate(self.lines[di][o], 1):
                ops.append((self.n.order_line(w, dd, o, line), None))
                seen.setdefault(i, None)
        ops += [(self.n.stock(w, i), None) for i in seen]
        return ops


def build(config: dict, mix: dict, seed: int) -> dict:
    rng = np.random.default_rng(mix["structure_seed"])
    gen = TPCC(config["data"], rng, Values(config["data"]["widths"], seed),
               Names(config["data"], seed))
    t = time.perf_counter()
    data = gen.dataset()
    data_s = time.perf_counter() - t
    backlog = [gen.transaction() for _ in range(mix["backlog"]["sessions"])]
    window = [gen.transaction() for _ in range(mix["window"]["sessions"])]
    return {"data": data, "data_s": data_s, "backlog": backlog,
            "window": window}
