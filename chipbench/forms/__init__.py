"""Forms: how a traffic mix drives the program.  A traffic file names its
form, and ``run.py`` finds ``forms/<form>.py`` by that name."""
