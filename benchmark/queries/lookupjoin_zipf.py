"""``queries/lookupjoin.py`` over the skewed stream
(``configs/orders-people-mesh4-zipf.json``): the same ``build`` (both
files through ``FromFile(...).OnDevice(platform, shards=N)``, the index
through ``UniqueIndexOn``, one ``PlanCache``, no ``CSVPLUS_*`` variable,
no class attribute, nothing that selects a tier) and the same ``want``
(``row_of[cust]`` gathers over the generator's arrays).

``verify`` adds two things to that file's.  It holds the generator to
its configuration: at full size the first rank places exactly the
configuration's ``hot_rows`` orders.  And it prints what the first
execution's stages say of the skew (``join:skew-detect``,
``join:broadcast``, ``join:skew``, ``join:all_to_all``).  It refuses no
program for HOW it handles the skew: the guarantee is the result, which
the full comparison holds to limit 0 whatever share of the rows one key
has; whether the hot-key tier engaged is what the cell's per-layer
metrics show.
"""

from __future__ import annotations

import reference as ref
from queries import lookupjoin as base

SKEW_STAGES = ("join:skew-detect", "join:broadcast", "join:skew", "join:all_to_all")

build = base.build
want = base.want


def verify(h, state, last, digests) -> None:
    d = h.data
    orders = h.cfg["tables"]["orders"]
    if d.n == int(orders["rows"]):
        ref.check(
            int(d.rank_rows[0]) == int(orders["hot_rows"]),
            f"the generator's first rank places {int(d.rank_rows[0])} orders, "
            f"the configuration says {orders['hot_rows']}",
        )
    top = d.rank_rows[:4]
    h.say(
        f"check: the first ranks place {top.tolist()} of {d.n} orders "
        f"(shares {[round(float(c) / d.n, 4) for c in top]}); {int((d.rank_rows > 0).sum())} customers occur"
    )
    for stage, extra in state.first_stages or ():
        if stage in SKEW_STAGES:
            h.say(f"check: first execution's {stage} {extra}")
    base.verify(h, state, last, digests)
