"""Where the program keeps what it generates: inside its own checkout.

Caches live under the repository root (listed in ``.gitignore``), never
in a shared temporary directory, so a run reads and writes nothing
around its checkout and two checkouts never hand each other state.
"""
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
CACHE = ROOT / ".cache"
