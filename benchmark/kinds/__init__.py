"""One module per traffic kind: how it is run and which end-to-end metrics it yields."""
