"""conv_roofline.zipf: see lib/readers.py (conv_roofline)."""
from lib.readers import conv_roofline as read  # noqa: F401
