"""Cell drivers: ``setup``, ``window``, ``check`` and ``control``."""
