"""One driver per verb; a traffic file names its driver by module name."""
