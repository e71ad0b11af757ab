"""Put the checkout and its ``src`` on ``sys.path`` for these tests."""
import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(CHECKOUT, "src"), CHECKOUT,
          os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)
