# Launchers: train/serve drivers.
