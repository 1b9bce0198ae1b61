"""`pytest benchmark/tests` from the root of the checkout.  The tests of
the yardstick itself: they are not part of the repo's tier-1 (`tests/`)."""

import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)
