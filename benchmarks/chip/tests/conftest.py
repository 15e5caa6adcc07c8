import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (os.path.join(ROOT, "src"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)
