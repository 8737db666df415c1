"""One module a kind of run, found by the ``kind`` of a traffic mix: see
``train_records.py`` for what a kind provides."""
