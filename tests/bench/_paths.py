"""Puts the checkout's root on ``sys.path`` so that ``bench`` imports
(tier-1 puts only ``src`` there)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
