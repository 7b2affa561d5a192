"""The stake laws a configuration may name, as pure functions of its
`stake` group. Imports nothing of the program.

    {"law": "capped-zipf", "exponent": 1, "cap_weight": "1/18"}

The pool of rank r = 1..n weighs min(1 / r**exponent, cap_weight): a Zipf
tail under a saturation cap, as Cardano mainnet's stake lies under the
point where a pool holds 1/k of it (k = 500, protocol parameter nOpt).
The stakes are the weights over their sum, exact, in rank order.
"""

from __future__ import annotations

from fractions import Fraction


def capped_zipf(n: int, exponent: int, cap_weight: Fraction) -> list:
    weights = [min(Fraction(1, r ** exponent), cap_weight)
               for r in range(1, n + 1)]
    total = sum(weights)
    return [w / total for w in weights]


def stakes(group: dict, n: int) -> list:
    """-> the n pools' stakes by the configuration's `stake` group."""
    if group["law"] != "capped-zipf":
        raise ValueError(f"unknown stake law {group['law']!r}")
    return capped_zipf(n, int(group["exponent"]),
                       Fraction(group["cap_weight"]))
