"""padded_slot_share: see lib/readers.py."""
from lib.readers import padded_slot_share as read  # noqa: F401
