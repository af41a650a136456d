"""LUX004/LUX005 fixture: zero findings expected — declared flags read
through the registry accessors; environment WRITES stay legal."""
import os

from lux_tpu.utils import flags

LEVEL = flags.get("LUX_LOG")
SCALE = flags.get_int("LUX_SMOKE_SCALE")
os.environ.setdefault("LUX_NATIVE_CACHE", "/tmp")  # write, not a read
os.environ["LUX_LOG"] = "DEBUG"                # store context: legal
