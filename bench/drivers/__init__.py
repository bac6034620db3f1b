"""Part of the chip benchmark (see ``bench/__init__.py``)."""
