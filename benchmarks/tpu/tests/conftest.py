import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SRC = os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src")
for p in (HERE, BENCH, SRC):
    if p not in sys.path:
        sys.path.insert(0, p)
