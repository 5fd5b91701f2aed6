"""General runners, one per kind of traffic mix (the ``kind`` of a traffic file)."""
