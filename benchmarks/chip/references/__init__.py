"""Plain float32 reference forwards, one file per ``model_type``
(``<model_type>.py``), with the helpers they share (``common.py``)."""
