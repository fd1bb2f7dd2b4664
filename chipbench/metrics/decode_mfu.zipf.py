"""decode_mfu.zipf: see lib/readers.py (decode_mfu)."""
from lib.readers import decode_mfu as read  # noqa: F401
