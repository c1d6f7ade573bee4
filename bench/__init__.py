"""The chip benchmark of the Stars graph build (see ``bench/run.py``)."""
