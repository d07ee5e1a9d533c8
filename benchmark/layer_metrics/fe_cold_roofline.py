"""``fe_cold_roofline``: see ``fe_hot_roofline.py``, whose reader serves both
parts (``run.py`` finds a reader by the metric's name)."""

from fe_hot_roofline import read  # noqa: F401
