"""The store's ADT as its users see it, written down independently of the
program: operation codes, sentinels and what each operation returns.

``run.py`` checks at start-up that the program's public constants
(``repro.api``) agree with these, so a renumbering there fails loudly
instead of silently changing what the reference computes.
"""

OP_INSERT = 0
OP_DELETE = 1
OP_SEARCH = 2
OP_NOP = 3
OP_RANGE = 4

KEY_MAX = 2**31 - 1            # padding sentinel; user keys are below KEY_MAX - 1
KEY_DOMAIN_HI = KEY_MAX - 2    # largest user key
TOMBSTONE = -(2**31) + 1       # the value a DELETE writes
NOT_FOUND = -1                 # what a read of an absent key returns

NAMES = ("OP_INSERT", "OP_DELETE", "OP_SEARCH", "OP_NOP", "OP_RANGE",
         "KEY_MAX", "KEY_DOMAIN_HI", "TOMBSTONE", "NOT_FOUND")


def disagreements(api) -> list:
    """Names whose value in the program's ``api`` module differs."""
    return [n for n in NAMES if getattr(api, n) != globals()[n]]
