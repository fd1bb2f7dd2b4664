"""decode_share: see lib/readers.py."""
from lib.readers import decode_share as read  # noqa: F401
