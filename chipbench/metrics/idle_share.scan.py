"""idle_share.scan: see lib/readers.py (idle_share)."""
from lib.readers import idle_share as read  # noqa: F401
